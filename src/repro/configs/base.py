"""Architecture + shape configuration dataclasses.

Every assigned architecture (plus the paper's own evaluation networks) is a
frozen ``ArchConfig``.  A config is pure data: the model zoo in
``repro.models`` interprets it, the launcher lowers it, and the ADAPTOR core
(``repro.core``) builds runtime-adaptive engines whose *maxima* are taken from
one of these configs.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any

import jax.numpy as jnp


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block configuration."""

    num_experts: int
    experts_per_token: int
    expert_d_ff: int
    num_shared_experts: int = 0
    shared_expert_d_ff: int = 0
    # Layers [0, first_k_dense) use a dense FFN of size ``dense_d_ff`` instead
    # of the MoE block (DeepSeek-V3 uses 3 dense layers).
    first_k_dense: int = 0
    dense_d_ff: int = 0
    # Gate weights of the k picked experts, normalized over the k, are
    # scaled by this (DeepSeek's routed_scaling_factor).
    router_scale: float = 1.0
    # "softmax": top-k of softmax(logits) (Mixtral / granite).  "sigmoid":
    # DeepSeek-V3's noaux_tc router - sigmoid scores plus a per-expert
    # correction bias that only selects: the experts fall into ``n_group``
    # groups, a group scores the sum of its top two, the ``topk_group``
    # best groups are kept and the top k is taken inside them.
    scoring: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    # The experts this chip holds: ids [expert_offset, expert_offset +
    # held_experts) of the router's num_experts (0: all of them).  The
    # router scores every expert; only the held ones are computed.
    expert_offset: int = 0
    held_experts: int = 0

    def __post_init__(self) -> None:
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown MoE scoring {self.scoring!r}")
        if self.num_experts % self.n_group \
                or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(
                f"n_group={self.n_group} must divide num_experts="
                f"{self.num_experts} and topk_group={self.topk_group} lie "
                "in [1, n_group]")
        if self.expert_offset < 0 \
                or self.expert_offset + self.n_held > self.num_experts:
            raise ValueError(
                f"held experts [{self.expert_offset}, {self.expert_offset}"
                f" + {self.n_held}) exceed num_experts={self.num_experts}")

    @property
    def n_held(self) -> int:
        """Experts whose weights this chip holds and computes."""
        return self.held_experts or self.num_experts


@dataclass(frozen=True)
class RopeScaling:
    """YaRN rotary scaling (arXiv:2309.00071), in DeepSeek-V3's form.

    Rotary pairs that turn fewer than ``beta_slow`` times over
    ``original_max_position_embeddings`` positions have their frequency
    divided by ``factor``, pairs that turn more than ``beta_fast`` times
    keep it, and a linear ramp blends the pairs between.  cos/sin are multiplied by
    mscale(factor, mscale) / mscale(factor, mscale_all_dim), and MLA's
    softmax scale by mscale(factor, mscale_all_dim) squared, with
    mscale(s, m) = 0.1 m ln s + 1.
    """

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V3) configuration."""

    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-1 selective-SSM configuration."""

    state_dim: int = 16
    conv_kernel: int = 4
    expand: int = 2
    dt_rank: int = 0  # 0 -> ceil(d_model / 16)


@dataclass(frozen=True)
class HybridConfig:
    """RecurrentGemma-style hybrid (RG-LRU + local attention) configuration."""

    # Block pattern, repeated over depth: 'r' = RG-LRU block, 'a' = local attn.
    pattern: tuple[str, ...] = ("r", "r", "a")
    lru_width: int = 0  # 0 -> d_model
    attention_window: int = 2048


@dataclass(frozen=True)
class EncDecConfig:
    """Encoder/decoder (Whisper) configuration."""

    num_encoder_layers: int
    # Length of the (stub) frontend output fed to the encoder.  For Whisper
    # this is n_audio_frames / 2 after the conv stack.
    encoder_seq_len: int = 1500


@dataclass(frozen=True)
class FrontendConfig:
    """Stub modality frontend: ``input_specs`` provides precomputed embeddings."""

    kind: str  # 'vision' | 'audio'
    num_tokens: int  # patch / frame token count delivered by the stub
    feature_dim: int  # embedding dim delivered by the stub (== d_model)


@dataclass(frozen=True)
class ArchConfig:
    """A single architecture from the assigned pool (or the paper's own)."""

    name: str
    family: str  # dense | moe | vlm | ssm | hybrid | audio | encoder
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads
    activation: str = "swiglu"  # swiglu | geglu | gelu | relu
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    positional: str = "rope"  # rope | learned | none
    tie_embeddings: bool = False
    max_position_embeddings: int = 131_072
    rope_scaling: RopeScaling | None = None  # None: plain rotary
    source: str = ""  # provenance tag from the assignment table

    moe: MoEConfig | None = None
    mla: MLAConfig | None = None
    ssm: SSMConfig | None = None
    hybrid: HybridConfig | None = None
    encdec: EncDecConfig | None = None
    frontend: FrontendConfig | None = None
    # Multi-token prediction depth (DeepSeek-V3 MTP); 0 disables.
    num_mtp_modules: int = 0

    def __post_init__(self) -> None:
        self.validate()

    def validate(self) -> "ArchConfig":
        """Construction-time shape sanity: the mistakes rejected here used
        to surface as cryptic reshape errors deep inside jit."""
        if self.num_heads > 0:
            # the paper's own encoder networks use deliberately odd dims
            # (custom-encoder: 200/3) and define head_dim = floor(d/h); the
            # decode families have no such convention, so reject there
            if self.head_dim == 0 and self.mla is None \
                    and self.family != "encoder" \
                    and self.d_model % self.num_heads:
                raise ValueError(
                    f"{self.name}: d_model={self.d_model} is not divisible "
                    f"by num_heads={self.num_heads} (and no explicit "
                    "head_dim is set); pick a head count that divides "
                    "d_model or set head_dim explicitly")
            if self.num_kv_heads <= 0 or self.num_heads % self.num_kv_heads:
                raise ValueError(
                    f"{self.name}: num_kv_heads={self.num_kv_heads} must be "
                    f"a positive divisor of num_heads={self.num_heads} "
                    "(each KV head serves an equal group of query heads)")
        return self

    # ---- derived ----------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_full_attention_decode(self) -> bool:
        return self.family != "encoder"

    @property
    def is_subquadratic(self) -> bool:
        """True if serve-time cost is sub-quadratic in sequence length."""
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Analytical parameter count (embedding + per-layer), used by the
        roofline model's 6·N·D term and by DESIGN/EXPERIMENTS reporting."""
        from repro.core.analytical import arch_param_count

        return arch_param_count(self)

    def active_param_count(self) -> int:
        from repro.core.analytical import arch_param_count

        return arch_param_count(self, active_only=True)


@dataclass(frozen=True)
class ShapeSpec:
    """One input-shape cell from the assignment (seq_len x global_batch)."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


# The four assigned LM shapes.  ``decode_*`` / ``long_*`` lower ``serve_step``
# (one new token against a KV cache of seq_len), not ``train_step``.
TRAIN_4K = ShapeSpec("train_4k", 4_096, 256, "train")
PREFILL_32K = ShapeSpec("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = ShapeSpec("decode_32k", 32_768, 128, "decode")
LONG_500K = ShapeSpec("long_500k", 524_288, 1, "decode")

ALL_SHAPES: tuple[ShapeSpec, ...] = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
SHAPES_BY_NAME = {s.name: s for s in ALL_SHAPES}


def cell_is_applicable(cfg: ArchConfig, shape: ShapeSpec) -> tuple[bool, str]:
    """Whether an (arch x shape) cell runs, and if not, why (for the report).

    Per assignment: ``long_500k`` needs sub-quadratic attention -> skipped for
    pure full-attention archs; encoder-only archs have no decode step.
    """
    if shape.name == "long_500k" and not cfg.is_subquadratic:
        return False, "skip: full quadratic attention at 512k context"
    if shape.is_decode and not cfg.supports_full_attention_decode:
        return False, "skip: encoder-only arch has no decode step"
    return True, ""


def reduced(cfg: ArchConfig, **overrides: Any) -> ArchConfig:
    """A tiny same-family config for CPU smoke tests.

    Keeps every structural feature (GQA ratio, MoE routing, MLA, SSM, hybrid
    pattern, enc-dec) while shrinking width/depth/vocab.
    """
    small: dict[str, Any] = dict(
        num_layers=min(cfg.num_layers, 2),
        d_model=64,
        d_ff=128,
        vocab_size=128,
        max_position_embeddings=512,
    )
    # Preserve the GQA grouping ratio with >=1 kv head.
    ratio = max(1, cfg.num_heads // max(cfg.num_kv_heads, 1))
    heads = 4
    small["num_heads"] = heads
    small["num_kv_heads"] = max(1, heads // ratio)
    small["head_dim"] = 16
    if cfg.moe is not None:
        small["moe"] = MoEConfig(
            num_experts=4,
            experts_per_token=min(2, cfg.moe.experts_per_token),
            expert_d_ff=32,
            num_shared_experts=cfg.moe.num_shared_experts,
            shared_expert_d_ff=32 if cfg.moe.num_shared_experts else 0,
            first_k_dense=min(1, cfg.moe.first_k_dense),
            dense_d_ff=128 if cfg.moe.first_k_dense else 0,
            # the router's scoring and scale; 4 experts make no groups
            # (group limiting is tested at 16 experts in 4 groups)
            router_scale=cfg.moe.router_scale,
            scoring=cfg.moe.scoring,
        )
    if cfg.mla is not None:
        small["mla"] = MLAConfig(
            q_lora_rank=32,
            kv_lora_rank=16,
            qk_nope_head_dim=16,
            qk_rope_head_dim=8,
            v_head_dim=16,
        )
    if cfg.ssm is not None:
        small["ssm"] = SSMConfig(state_dim=8, conv_kernel=4, expand=2, dt_rank=8)
    if cfg.hybrid is not None:
        small["hybrid"] = HybridConfig(
            pattern=cfg.hybrid.pattern, lru_width=0, attention_window=32
        )
        small["num_layers"] = len(cfg.hybrid.pattern)  # one full pattern period
    if cfg.encdec is not None:
        small["encdec"] = EncDecConfig(num_encoder_layers=2, encoder_seq_len=16)
    if cfg.frontend is not None:
        small["frontend"] = FrontendConfig(
            kind=cfg.frontend.kind, num_tokens=8, feature_dim=64
        )
    if cfg.num_mtp_modules:
        small["num_mtp_modules"] = 1
    small.update(overrides)
    return dataclasses.replace(cfg, **small)


DEFAULT_PARAM_DTYPE = jnp.float32
DEFAULT_COMPUTE_DTYPE = jnp.bfloat16
