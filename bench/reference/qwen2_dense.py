"""Plain forward pass of a Qwen2-style dense decoder, in float32.

Qwen1.5 and CodeQwen1.5 share this block (Hugging Face ``Qwen2Model``):
RMSNorm -> attention with biased q/k/v projections, rotary embeddings
on the two halves of each head, grouped K/V heads -> residual ->
RMSNorm -> SwiGLU FFN -> residual; a final RMSNorm and the vocabulary
head (the embedding itself where tied).  No cache, no paging, no
batching: one packed row of whole sequences at a time, each token
attending to the earlier tokens of its own sequence.  Weights are the
benchmark's (``bench.families.qwen2_dense.make``), read in float32 with
matmuls at the highest precision.  Imports nothing of the serving
program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PREC = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
HEAD_BLOCK = 512


def _mm(a, b):
    return jnp.matmul(a, b, precision=PREC)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, pos, theta):
    """x [S, heads, hd]: rotate (first half, second half) pairs."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, seg):
    """q [S, kv, rep, hd], k/v [S, kv, hd]; causal within a segment."""
    s_len, kvh, rep, hd = q.shape
    size = min(QUERY_BLOCK, s_len)
    idx = jnp.arange(s_len)

    def block(b):
        lo = b * size
        qb = jax.lax.dynamic_slice_in_dim(q, lo, size)
        qi = jax.lax.dynamic_slice_in_dim(idx, lo, size)
        qs = jax.lax.dynamic_slice_in_dim(seg, lo, size)
        s = jnp.einsum("qkrd,skd->krqs", qb, k, precision=PREC)
        s = s / math.sqrt(hd)
        ok = (qs[:, None] == seg[None, :]) & (idx[None, :] <= qi[:, None])
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("krqs,skd->qkrd", p, v, precision=PREC)

    out = jax.lax.map(block, jnp.arange(s_len // size))
    return out.reshape(s_len, kvh * rep * hd)


def hidden(cfg: dict, w: dict, tokens, pos, seg):
    """Final-normed hidden states [S, d] of one packed row."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kvh = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim", d // h)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    s_len = tokens.shape[0]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    x = f32(w["embed"])[tokens]

    def layer(x, lw):
        lw = jax.tree.map(f32, lw)
        a = _rms(x, lw["ln1"], eps)
        q = (_mm(a, lw["wq"]) + lw["bq"]).reshape(s_len, h, hd)
        k = (_mm(a, lw["wk"]) + lw["bk"]).reshape(s_len, kvh, hd)
        v = (_mm(a, lw["wv"]) + lw["bv"]).reshape(s_len, kvh, hd)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        o = _attention(q.reshape(s_len, kvh, h // kvh, hd), k, v, seg)
        x = x + _mm(o, lw["wo"])
        a = _rms(x, lw["ln2"], eps)
        g = jax.nn.silu(_mm(a, lw["w_gate"])) * _mm(a, lw["w_up"])
        return x + _mm(g, lw["w_down"]), None

    names = ("ln1", "ln2", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
             "w_gate", "w_up", "w_down")
    x, _ = jax.lax.scan(layer, x, {n: w[n] for n in names})
    return _rms(x, f32(w["final_norm"]), eps)


def gaps(cfg: dict, w: dict, tokens, pos, seg, targets):
    """For every row position: the best logit less the logit of
    ``targets`` there, and the best token.  ``tokens``/``pos``/``seg``/
    ``targets`` are [S] int32, S a multiple of 512 or below it."""
    x = hidden(cfg, w, tokens, pos, seg)
    table = w["lm_head"] if "lm_head" in w else w["embed"]
    size = min(HEAD_BLOCK, tokens.shape[0])

    def block(b):
        xb = jax.lax.dynamic_slice_in_dim(x, b * size, size)
        tb = jax.lax.dynamic_slice_in_dim(targets, b * size, size)
        lg = jnp.einsum("sd,vd->sv", xb, table.astype(jnp.float32),
                        precision=PREC)
        best = lg.max(-1)
        got = jnp.take_along_axis(lg, tb[:, None], axis=-1)[:, 0]
        return best - got, lg.argmax(-1).astype(jnp.int32)

    gap, top = jax.lax.map(block, jnp.arange(tokens.shape[0] // size))
    return gap.reshape(-1), top.reshape(-1)
