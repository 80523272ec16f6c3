"""Shared neural layers: norms, activations, RoPE, projections.

Pure-functional JAX; every matmul routes through ``dense`` so the ADAPTOR
tiled-kernel path (``repro.kernels``) and the XLA path are interchangeable via
``repro.models.backend``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import backend


# --------------------------------------------------------------------------
# Normalization (paper §3.5 — the LN unit)
# --------------------------------------------------------------------------
def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + 0.0) * weight.astype(jnp.float32)).astype(dt)


def layernorm(x: jax.Array, weight: jax.Array, bias: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def apply_norm(x: jax.Array, p: dict, kind: str) -> jax.Array:
    with jax.named_scope("norm"):
        if kind == "rmsnorm":
            return rmsnorm(x, p["scale"])
        return layernorm(x, p["scale"], p["bias"])


def build_norm(b, d: int, kind: str) -> dict:
    if kind == "rmsnorm":
        return {"scale": b.param((d,), ("embed",), init="ones")}
    return {"scale": b.param((d,), ("embed",), init="ones"),
            "bias": b.param((d,), ("embed",), init="zeros")}


# --------------------------------------------------------------------------
# Activations (paper §3.4 — activation unit; Eq. 5-7)
# --------------------------------------------------------------------------
def activate(x: jax.Array, kind: str) -> jax.Array:
    if kind == "relu":
        return jax.nn.relu(x)
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if kind in ("swiglu", "silu"):
        return jax.nn.silu(x)
    if kind == "geglu":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown activation {kind!r}")


def is_gated(kind: str) -> bool:
    return kind in ("swiglu", "geglu")


# --------------------------------------------------------------------------
# Dense projection — single entry point for all matmuls
# --------------------------------------------------------------------------
def dense(x: jax.Array, w, bias: jax.Array | None = None) -> jax.Array:
    """y = x @ w (+ bias).  Routed through the active matmul backend so the
    ADAPTOR Pallas tiled kernel can replace XLA dot on TPU.  ``w`` may be
    an int8 ``QTensor`` (the paper's C6 serving path): the weight is read
    from HBM at 1 byte/elem and dequantized on the fly (fused on TPU)."""
    from repro.core.quant import QTensor

    if isinstance(w, QTensor):
        if backend.active_impl() == "pallas" and w.values.ndim == 2:
            # deployment path: dynamic activation quant + the C6 int8
            # Pallas kernel — the weight never leaves int8 on the wire
            from repro.kernels import ops

            y = ops.quantized_dense(x, w)
            if bias is not None:
                y = y + bias.astype(y.dtype)
            return y
        w = w.values.astype(x.dtype) * w.scale.astype(x.dtype)
    y = backend.matmul(x, w)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def build_dense(b, d_in: int, d_out: int, axes: tuple[str | None, str | None],
                use_bias: bool = False, name_axes_bias: str | None = None) -> dict:
    p = {"kernel": b.param((d_in, d_out), axes)}
    if use_bias:
        p["bias"] = b.param((d_out,), (name_axes_bias if name_axes_bias else axes[1],),
                            init="zeros")
    return p


def apply_dense(x: jax.Array, p: dict) -> jax.Array:
    from repro.core.quant import QTensor

    k = p["kernel"]
    if not isinstance(k, QTensor):
        k = k.astype(x.dtype)
    return dense(x, k, p.get("bias"))


# --------------------------------------------------------------------------
# Rotary position embeddings
# --------------------------------------------------------------------------
def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: 0.1 m ln s + 1 (1 where s <= 1)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _yarn_range(scaling, dim: int, theta: float) -> tuple[int, int]:
    """The rotary pairs between ``beta_fast`` and ``beta_slow`` rotations
    over the original context: [low, high], the published correction
    range (floor / ceil, clipped to the pairs)."""
    def pair(rotations):
        return dim * math.log(scaling.original_max_position_embeddings
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = math.floor(pair(scaling.beta_fast))
    high = math.ceil(pair(scaling.beta_slow))
    return max(low, 0), min(high, dim - 1)


def rope_frequencies(head_dim: int, theta: float,
                     scaling=None) -> jax.Array:
    """Inverse frequencies of the ``head_dim // 2`` rotary pairs; under
    YaRN (``configs.base.RopeScaling``) pairs below the correction range
    keep theirs, pairs above are divided by ``factor``, and a linear ramp
    blends the pairs between."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if scaling is None:
        return inv
    low, high = _yarn_range(scaling, head_dim, theta)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return inv / scaling.factor * ramp + inv * (1.0 - ramp)


def rope_mscale(scaling) -> float:
    """The factor on cos/sin: 1 without scaling."""
    if scaling is None:
        return 1.0
    return yarn_mscale(scaling.factor, scaling.mscale) \
        / yarn_mscale(scaling.factor, scaling.mscale_all_dim)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling=None) -> jax.Array:
    """x: [..., S, H, D]; positions: broadcastable to [..., S].  Rotates
    (first half, second half) pairs; ``scaling`` is the config's
    ``rope_scaling`` (None: plain rotary)."""
    d = x.shape[-1]
    inv_freq = rope_frequencies(d, theta, scaling)  # [D/2]
    angles = positions[..., :, None].astype(jnp.float32) * inv_freq  # [..., S, D/2]
    cos = jnp.cos(angles)[..., :, None, :]  # [..., S, 1, D/2]
    sin = jnp.sin(angles)[..., :, None, :]
    if scaling is not None:
        m = rope_mscale(scaling)
        cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Embedding
# --------------------------------------------------------------------------
def build_embedding(b, vocab: int, d: int) -> dict:
    return {"table": b.param((vocab, d), ("vocab", "embed"), scale=0.02)}


def maybe_dequant(table, dtype):
    from repro.core.quant import QTensor

    if isinstance(table, QTensor):
        return table.values.astype(dtype) * table.scale.astype(dtype)
    return table.astype(dtype)


def embed(tokens: jax.Array, p: dict, dtype=jnp.bfloat16) -> jax.Array:
    from repro.core.quant import QTensor

    t = p["table"]
    with jax.named_scope("embed"):
        if isinstance(t, QTensor):  # per-row int8: gather rows + row scales
            return (t.values[tokens].astype(dtype)
                    * t.scale[tokens].astype(dtype))
        return t.astype(dtype)[tokens]


def unembed(x: jax.Array, p: dict) -> jax.Array:
    """Logits = x @ table^T, in f32 for a stable softmax/xent."""
    table = maybe_dequant(p["table"], jnp.float32)
    return jnp.einsum("...d,vd->...v", x.astype(jnp.float32), table)
