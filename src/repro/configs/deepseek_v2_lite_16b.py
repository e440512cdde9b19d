"""DeepSeek-V2-Lite 16B [arXiv:2405.04434; hf].

27L d_model=2048 16H, MLA (kv_lora=512), MoE 64 routed top-6 + 2 shared,
d_expert=1408, vocab 102400. First layer uses a dense FFN (per the HF
config: first_k_dense_replace=1), remaining 26 layers are MoE. The gate is
a softmax over the 64 experts whose top-6 weights are used raw
(norm_topk_prob false, routed_scaling_factor 1); MLA's rope part is
YaRN-scaled (factor 40, mscale 0.707 / 0.707, 4,096 original positions).
"""
from repro.configs.base import (
    LayerSpec,
    MLASpec,
    ModelConfig,
    MoESpec,
    TrainSpec,
    YarnSpec,
    register_arch,
)

CONFIG = register_arch(
    ModelConfig(
        name="deepseek-v2-lite-16b",
        family="moe",
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        head_dim=192,  # qk_nope(128) + qk_rope(64)
        d_ff=10944,  # dense first layer
        vocab_size=102400,
        prefix=(LayerSpec("attn", "dense"),),
        pattern=(LayerSpec("attn", "moe"),),
        num_periods=26,
        mla=MLASpec(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
        moe=MoESpec(num_experts=64, top_k=6, d_expert=1408, num_shared=2,
                    norm_topk_prob=False, routed_scaling_factor=1.0),
        rope_theta=10000.0,
        rope_scaling=YarnSpec(factor=40.0, original_max_position=4096, beta_fast=32.0, beta_slow=1.0,
                              mscale=0.707, mscale_all_dim=0.707),
        train=TrainSpec(optimizer="adamw", microbatches=4, remat=True, dp_shard_params=True),
        notes="MLA caches the 512-dim latent + 64-dim rope key instead of full KV.",
    )
)
