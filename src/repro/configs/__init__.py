from repro.configs.base import (
    ARCH_REGISTRY,
    SHAPES,
    LayerSpec,
    MLASpec,
    MambaSpec,
    ModelConfig,
    MoESpec,
    ShapeSpec,
    TrainSpec,
    YarnSpec,
    get_config,
    register_arch,
    supports_shape,
)

# Importing the arch modules populates ARCH_REGISTRY.
from repro.configs import (  # noqa: F401  (registration side effects)
    command_r_35b,
    deepseek_v2_lite_16b,
    gemma2_2b,
    granite_moe_3b_a800m,
    hubert_xlarge,
    jamba_1_5_large_398b,
    llama3_2_1b,
    llama3_405b,
    paper_tasks,
    pixtral_12b,
    tiny_lm,
    xlstm_1_3b,
)
from repro.configs.tiny_lm import TINY_LM

__all__ = [
    "ARCH_REGISTRY",
    "TINY_LM",
    "SHAPES",
    "LayerSpec",
    "MLASpec",
    "MambaSpec",
    "ModelConfig",
    "MoESpec",
    "YarnSpec",
    "ShapeSpec",
    "TrainSpec",
    "get_config",
    "register_arch",
    "supports_shape",
]
