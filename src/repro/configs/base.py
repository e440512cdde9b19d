"""Config system: one declarative ModelConfig covers all 10 assigned
architecture families (dense / MoE / MLA / VLM / audio-encoder / hybrid
Mamba / xLSTM).

A model is ``prefix`` (unrolled layers) followed by ``pattern`` repeated
``num_periods`` times (scanned — keeps HLO size O(1) in depth for the
126-layer models). Each layer is a (mixer, ffn) pair:

  mixer: "attn" | "attn_local" | "mamba" | "mlstm" | "slstm"
  ffn:   "dense" | "moe" | "none"
"""
from __future__ import annotations

import dataclasses
import math
from typing import Literal

Mixer = Literal["attn", "attn_local", "mamba", "mlstm", "slstm"]
Ffn = Literal["dense", "moe", "none"]


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: Mixer = "attn"
    ffn: Ffn = "dense"


@dataclasses.dataclass(frozen=True)
class MoESpec:
    """A mixture-of-experts FFN. ``num_experts`` is the router's width: the
    experts every token is routed over. A chip of an expert-parallel
    deployment holds the ``held`` experts from id ``held_first`` on (all of
    them when ``held`` is None) and computes their part of the result."""

    num_experts: int
    top_k: int
    d_expert: int
    num_shared: int = 0  # shared (always-on) experts, DeepSeek-style
    router_noise: float = 0.0
    norm_topk_prob: bool = True  # renormalize the top-k gate weights to sum 1
    routed_scaling_factor: float = 1.0
    held_first: int = 0
    held: int | None = None

    @property
    def num_held(self) -> int:
        return self.num_experts if self.held is None else self.held


@dataclasses.dataclass(frozen=True)
class MLASpec:
    """DeepSeek-V2 multi-head latent attention."""

    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    q_lora_rank: int | None = None  # V2-Lite projects q directly


@dataclasses.dataclass(frozen=True)
class YarnSpec:
    """YaRN rotary scaling (DeepSeek-V2's ``rope_scaling`` of type yarn)."""

    factor: float = 40.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclasses.dataclass(frozen=True)
class MambaSpec:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model


@dataclasses.dataclass(frozen=True)
class TrainSpec:
    """Execution policy knobs that make each (arch x shape) cell fit + run fast.

    These are the §Perf levers: microbatching bounds activation memory,
    remat bounds residual memory, the optimizer choice bounds state memory
    (adafactor for the 400B-class models), and dp_shard_params turns on
    ZeRO/FSDP-style parameter+state sharding over the data axis.
    """

    optimizer: Literal["adamw", "adafactor", "sgdm"] = "adamw"
    microbatches: int = 1
    remat: bool = True
    dp_shard_params: bool = False
    learning_rate: float = 3e-4


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | vlm | audio | hybrid | ssm
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    pattern: tuple[LayerSpec, ...]
    num_periods: int
    prefix: tuple[LayerSpec, ...] = ()
    head_dim: int | None = None
    moe: MoESpec | None = None
    mla: MLASpec | None = None
    mamba: MambaSpec | None = None
    causal: bool = True
    is_encoder: bool = False
    sliding_window: int = 4096
    rope_theta: float = 10000.0
    rope_scaling: YarnSpec | None = None
    final_logit_softcap: float | None = None
    attn_logit_softcap: float | None = None
    query_pre_attn_scalar: float | None = None  # gemma2: fixed 1/sqrt(256) scale
    use_post_norm: bool = False  # gemma2 applies RMSNorm after mixer/ffn too
    tie_embeddings: bool = False
    embeds_input: bool = False  # vlm/audio: frontend stub feeds embeddings
    # dropless MoE: every (token, expert) pair routed to a held expert is
    # computed (``layers.apply_moe_held``), so no token ever overflows. It
    # makes the full forward consistent with incremental decode, and is the
    # only layer that holds a share of the experts; without it training
    # keeps capacity_factor dispatch over all experts.
    moe_dropless: bool = False
    norm_eps: float = 1e-6
    train: TrainSpec = TrainSpec()
    # xLSTM block internals
    mlstm_proj_factor: float = 2.0
    slstm_proj_factor: float = 4.0 / 3.0
    notes: str = ""

    # ---- derived ----
    @property
    def num_layers(self) -> int:
        return len(self.prefix) + len(self.pattern) * self.num_periods

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.num_heads

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 256 so TP=16 shards evenly."""
        return math.ceil(self.vocab_size / 256) * 256

    @property
    def all_layers(self) -> tuple[LayerSpec, ...]:
        return self.prefix + self.pattern * self.num_periods

    @property
    def subquadratic(self) -> bool:
        """True if decode state stays O(1)-ish in sequence length (SSM/hybrid)."""
        mixers = {layer.mixer for layer in self.all_layers}
        return mixers.issubset({"mamba", "mlstm", "slstm"}) or (
            self.family in ("hybrid", "ssm")
        )

    def param_count(self) -> int:
        """Analytic parameter count (used by roofline MODEL_FLOPS and comm accounting)."""
        d, hd = self.d_model, self.resolved_head_dim
        n = self.padded_vocab * d  # embed
        if not self.tie_embeddings:
            n += d * self.padded_vocab
        for layer in self.all_layers:
            n += self._mixer_params(layer.mixer, d, hd)
            n += self._ffn_params(layer.ffn, d)
            n += 2 * d  # norms
        n += d  # final norm
        return n

    def active_param_count(self) -> int:
        """Per-token active params (MoE top-k only) — for 6*N_active*D."""
        d, hd = self.d_model, self.resolved_head_dim
        n = self.padded_vocab * d
        if not self.tie_embeddings:
            n += d * self.padded_vocab
        for layer in self.all_layers:
            n += self._mixer_params(layer.mixer, d, hd)
            if layer.ffn == "moe":
                assert self.moe is not None
                active = min(self.moe.top_k, self.moe.num_held) + self.moe.num_shared
                n += active * 3 * d * self.moe.d_expert + d * self.moe.num_experts
            else:
                n += self._ffn_params(layer.ffn, d)
            n += 2 * d
        n += d
        return n

    def _mixer_params(self, mixer: str, d: int, hd: int) -> int:
        if mixer in ("attn", "attn_local"):
            if self.mla is not None:
                m = self.mla
                qdim = self.num_heads * (m.qk_nope_head_dim + m.qk_rope_head_dim)
                n = d * qdim  # q proj (no lora in Lite)
                n += d * (m.kv_lora_rank + m.qk_rope_head_dim)  # compressed kv + rope k
                n += m.kv_lora_rank * self.num_heads * (m.qk_nope_head_dim + m.v_head_dim)
                n += self.num_heads * m.v_head_dim * d  # out proj
                return n
            q = d * self.num_heads * hd
            kv = 2 * d * self.num_kv_heads * hd
            o = self.num_heads * hd * d
            return q + kv + o
        if mixer == "mamba":
            assert self.mamba is not None
            di, ds, dc = self.mamba.d_inner(d), self.mamba.d_state, self.mamba.d_conv
            n = d * 2 * di  # in proj (x, z)
            n += di * dc  # conv
            n += di * (ds * 2 + 1) + di  # B, C, dt projections (x -> dt low rank simplified) + dt bias
            n += di * ds + di  # A_log, D
            n += di * d  # out proj
            return n
        if mixer == "mlstm":
            di = int(d * self.mlstm_proj_factor)
            n = d * 2 * di  # up proj (x, z)
            n += 3 * di * di // max(self.num_heads, 1)  # q,k,v block-diag proj (per-head)
            n += 3 * di  # i, f gates + norm
            n += di * d  # down proj
            return n
        if mixer == "slstm":
            di = d
            n = 4 * di * di + 4 * di * di  # input + recurrent weights (i,f,z,o)
            n += 4 * di
            n += int(d * self.slstm_proj_factor) * d * 2  # post-block FFN up/down
            return n
        raise ValueError(mixer)

    def _ffn_params(self, ffn: str, d: int) -> int:
        if ffn == "dense":
            return 3 * d * self.d_ff  # swiglu: gate, up, down
        if ffn == "moe":
            assert self.moe is not None
            total = (self.moe.num_held + self.moe.num_shared) * 3 * d * self.moe.d_expert
            total += d * self.moe.num_experts  # router
            return total
        if ffn == "none":
            return 0
        raise ValueError(ffn)


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


ARCH_REGISTRY: dict[str, ModelConfig] = {}


def register_arch(config: ModelConfig) -> ModelConfig:
    ARCH_REGISTRY[config.name] = config
    return config


def get_config(name: str) -> ModelConfig:
    if name not in ARCH_REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCH_REGISTRY)}")
    return ARCH_REGISTRY[name]


def supports_shape(config: ModelConfig, shape: ShapeSpec) -> tuple[bool, str]:
    """(supported, reason-if-not). Skip rules per the brief + DESIGN.md §4."""
    if config.is_encoder and shape.kind == "decode":
        return False, "encoder-only arch has no decode step"
    if shape.name == "long_500k" and not config.subquadratic:
        return False, "pure full-attention arch; 500k decode needs sub-quadratic state"
    return True, ""


def reduced_config(config: ModelConfig, d_model: int = 64, periods: int = 2) -> ModelConfig:
    """Tiny same-family config for CPU smoke tests (per-arch requirement)."""
    scale = d_model / config.d_model
    heads = max(2, min(config.num_heads, 4))
    kv = max(1, min(config.num_kv_heads, heads))
    kw: dict = dict(
        d_model=d_model,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=max(8, d_model // heads),
        d_ff=max(16, int(config.d_ff * scale)) if config.d_ff else 0,
        vocab_size=min(config.vocab_size, 512),
        num_periods=periods,
        prefix=config.prefix[: min(len(config.prefix), 1)],
        train=dataclasses.replace(config.train, microbatches=1, dp_shard_params=False),
    )
    if config.moe is not None:
        kw["moe"] = dataclasses.replace(
            config.moe, num_experts=4, top_k=min(config.moe.top_k, 2), d_expert=max(16, int(config.moe.d_expert * scale))
        )
    if config.mla is not None:
        kw["mla"] = MLASpec(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8)
        kw["head_dim"] = 8
    if config.mamba is not None:
        kw["mamba"] = dataclasses.replace(config.mamba, d_state=8)
    if config.sliding_window:
        kw["sliding_window"] = 16
    return dataclasses.replace(config, **kw)
