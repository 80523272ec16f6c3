"""Pure-jnp oracles for every Pallas kernel (the C-simulation analogue).

Each ``*_ref`` computes the same mathematical function as its kernel with
plain jnp ops; the kernel test suite sweeps shapes/dtypes and asserts
allclose between kernel (interpret mode) and oracle.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def tiled_matmul_ref(a: jax.Array, b: jax.Array, out_dtype=None) -> jax.Array:
    out_dtype = out_dtype or a.dtype
    return jnp.matmul(a.astype(jnp.float32),
                      b.astype(jnp.float32)).astype(out_dtype)


def qkv_proj_ref(x, wq, wk, wv):
    f = lambda w: jnp.matmul(x.astype(jnp.float32),
                             w.astype(jnp.float32)).astype(x.dtype)
    return f(wq), f(wk), f(wv)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True) -> jax.Array:
    """q: [BH, Sq, hd]; k/v: [BH, Skv, hd]."""
    hd = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    if causal:
        sq, skv = q.shape[1], k.shape[1]
        mask = jnp.arange(skv)[None, :] <= jnp.arange(sq)[:, None]
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)) \
        .astype(q.dtype)


def _act(x, kind):
    if kind == "relu":
        return jax.nn.relu(x)
    if kind in ("gelu", "geglu"):
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def ffn1_ref(x, w1, b1, activation="relu"):
    y = jnp.matmul(x.astype(jnp.float32), w1.astype(jnp.float32)) \
        + b1.astype(jnp.float32)
    return _act(y, activation).astype(x.dtype)


def ffn1_gated_ref(x, w1, wg, activation="swiglu"):
    y1 = jnp.matmul(x.astype(jnp.float32), w1.astype(jnp.float32))
    yg = jnp.matmul(x.astype(jnp.float32), wg.astype(jnp.float32))
    return (_act(yg, activation) * y1).astype(x.dtype)


def layernorm_ref(x, gamma, beta, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)) \
        .astype(x.dtype)


def rmsnorm_ref(x, gamma, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32)) \
        .astype(x.dtype)


def int8_matmul_ref(qx_values, qx_scale, qw_values, qw_scale,
                    out_dtype=jnp.bfloat16):
    acc = jnp.matmul(qx_values.astype(jnp.int32), qw_values.astype(jnp.int32))
    out = acc.astype(jnp.float32) * qx_scale * qw_scale.reshape(1, -1)
    return out.astype(out_dtype)


def mla_attend_ref(q_lat, q_rope, c_kv, k_rope, start, *, scale):
    """Absorbed latent attention as one einsum chain over the whole view:
    f32 scores ``[B, h, W, t_max]``, lane ``l`` of slot ``b`` masked to
    positions ``<= start[b] + l``, an f32 softmax, probabilities in
    ``c_kv.dtype`` against the latent view.  -> [B, W, h, rank]."""
    w, t_max = q_lat.shape[1], c_kv.shape[1]
    s_lat = jnp.einsum("bqhl,bkl->bhqk", q_lat.astype(jnp.float32),
                       c_kv.astype(jnp.float32))
    s_rope = jnp.einsum("bqhd,bkd->bhqk", q_rope.astype(jnp.float32),
                        k_rope.astype(jnp.float32))
    pos = start[:, None] + jnp.arange(w)[None, :]
    live = jnp.arange(t_max)[None, None, :] <= pos[:, :, None]
    s = jnp.where(live[:, None], (s_lat + s_rope) * scale, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkl->bqhl", p.astype(c_kv.dtype), c_kv)
