"""Config registry: ``get_config(name)`` / ``--arch <id>``.

The 10 assigned architectures plus the paper's own three evaluation networks.
"""
from __future__ import annotations

from repro.configs.base import (
    ALL_SHAPES,
    DECODE_32K,
    LONG_500K,
    PREFILL_32K,
    SHAPES_BY_NAME,
    TRAIN_4K,
    ArchConfig,
    EncDecConfig,
    FrontendConfig,
    HybridConfig,
    MLAConfig,
    MoEConfig,
    RopeScaling,
    SSMConfig,
    ShapeSpec,
    cell_is_applicable,
    reduced,
)

from repro.configs import (  # noqa: E402  (registry imports)
    adaptor_bert,
    codeqwen1_5_7b,
    custom_encoder,
    deepseek_v3_671b,
    falcon_mamba_7b,
    granite_moe_1b_a400m,
    phi3_mini_3_8b,
    phi_3_vision_4_2b,
    qwen1_5_0_5b,
    qwen2_72b,
    recurrentgemma_2b,
    shallow_transformer,
    whisper_medium,
)

# The 10 assigned pool architectures, in assignment order.
ASSIGNED: tuple[ArchConfig, ...] = (
    granite_moe_1b_a400m.CONFIG,
    deepseek_v3_671b.CONFIG,
    phi_3_vision_4_2b.CONFIG,
    qwen1_5_0_5b.CONFIG,
    qwen2_72b.CONFIG,
    phi3_mini_3_8b.CONFIG,
    codeqwen1_5_7b.CONFIG,
    falcon_mamba_7b.CONFIG,
    recurrentgemma_2b.CONFIG,
    whisper_medium.CONFIG,
)

# The paper's own evaluation networks (ADAPTOR §6, Table 1, Fig. 11).
PAPER_NETWORKS: tuple[ArchConfig, ...] = (
    adaptor_bert.CONFIG,
    shallow_transformer.CONFIG,
    custom_encoder.CONFIG,
)

ALL_CONFIGS: tuple[ArchConfig, ...] = ASSIGNED + PAPER_NETWORKS
REGISTRY: dict[str, ArchConfig] = {c.name: c for c in ALL_CONFIGS}


def get_config(name: str) -> ArchConfig:
    try:
        return REGISTRY[name]
    except KeyError:
        known = ", ".join(sorted(REGISTRY))
        raise KeyError(f"unknown arch {name!r}; known: {known}") from None


def get_shape(name: str) -> ShapeSpec:
    try:
        return SHAPES_BY_NAME[name]
    except KeyError:
        known = ", ".join(sorted(SHAPES_BY_NAME))
        raise KeyError(f"unknown shape {name!r}; known: {known}") from None


__all__ = [
    "ALL_CONFIGS",
    "ALL_SHAPES",
    "ASSIGNED",
    "ArchConfig",
    "DECODE_32K",
    "EncDecConfig",
    "FrontendConfig",
    "HybridConfig",
    "LONG_500K",
    "MLAConfig",
    "MoEConfig",
    "PAPER_NETWORKS",
    "PREFILL_32K",
    "REGISTRY",
    "SHAPES_BY_NAME",
    "SSMConfig",
    "ShapeSpec",
    "TRAIN_4K",
    "cell_is_applicable",
    "get_config",
    "get_shape",
    "reduced",
]
