"""jit'd public wrappers for the Pallas kernels.

* Block shapes default to the §3.10 tile planner (``core.tiling``) so the
  synthesis-time tile choice is automatic per shape, exactly as the paper
  fixes TS_MHA/TS_FFN per platform.
* ``interpret`` follows ``kernels.runtime.interpret_default()``: interpret
  mode off-TPU, so the whole suite validates on CPU; on TPU the same calls
  emit real Mosaic kernels.
* Leading batch dims are folded into the row dimension (the paper's
  SL-major layout).
"""
from __future__ import annotations

import functools

import jax

from repro.core.quant import QTensor, quantize_dynamic
from repro.core.tiling import plan_matmul
from repro.kernels import ffn as _ffn
from repro.kernels import flash_attention as _fa
from repro.kernels import int8_matmul as _i8
from repro.kernels import layernorm as _ln
from repro.kernels import qkv_proj as _qkv
from repro.kernels import tiled_matmul as _mm
from repro.kernels.runtime import interpret_default


@functools.cache
def _blocks(M: int, K: int, N: int, dtype_bytes: int = 2
            ) -> tuple[int, int, int]:
    p = plan_matmul(M, K, N, dtype_bytes)
    return p.bm, p.bk, p.bn


def _fold(x: jax.Array) -> tuple[jax.Array, tuple[int, ...]]:
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def tiled_matmul(x: jax.Array, w: jax.Array,
                 blocks: tuple[int, int, int] | None = None) -> jax.Array:
    """y[..., n] = x[..., k] w[k, n] via the Fig. 4 kernel."""
    x2, lead = _fold(x)
    bm, bk, bn = blocks or _blocks(x2.shape[0], w.shape[0], w.shape[1])
    y = _mm.tiled_matmul(x2, w, bm=bm, bk=bk, bn=bn, interpret=interpret_default())
    return y.reshape(lead + (w.shape[1],))


def qkv_proj(x: jax.Array, wq: jax.Array, wk: jax.Array, wv: jax.Array,
             blocks: tuple[int, int, int] | None = None):
    x2, lead = _fold(x)
    bm, bk, bn = blocks or _blocks(x2.shape[0], wq.shape[0],
                                   min(wq.shape[1], wk.shape[1]))
    q, k, v = _qkv.qkv_proj(x2, wq, wk, wv, bm=bm, bk=bk, bn=bn,
                            interpret=interpret_default())
    return (q.reshape(lead + (wq.shape[1],)),
            k.reshape(lead + (wk.shape[1],)),
            v.reshape(lead + (wv.shape[1],)))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, bq: int = 512,
                    bkv: int = 512) -> jax.Array:
    """q/k/v: [B, S, H, hd] (kv already head-repeated) -> [B, S, H, hd]."""
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Skv, hd)
    o = _fa.flash_attention(qf, kf, vf, causal=causal, bq=bq, bkv=bkv,
                            interpret=interpret_default())
    return o.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)


def ffn1(x: jax.Array, w1: jax.Array, b1: jax.Array,
         activation: str = "relu") -> jax.Array:
    x2, lead = _fold(x)
    bm, bk, bn = _blocks(x2.shape[0], w1.shape[0], w1.shape[1])
    y = _ffn.ffn1(x2, w1, b1, activation=activation, bm=bm, bk=bk, bn=bn,
                  interpret=interpret_default())
    return y.reshape(lead + (w1.shape[1],))


def ffn1_gated(x: jax.Array, w1: jax.Array, wg: jax.Array,
               activation: str = "swiglu") -> jax.Array:
    x2, lead = _fold(x)
    bm, bk, bn = _blocks(x2.shape[0], w1.shape[0], w1.shape[1])
    y = _ffn.ffn1_gated(x2, w1, wg, activation=activation, bm=bm, bk=bk,
                        bn=bn, interpret=interpret_default())
    return y.reshape(lead + (w1.shape[1],))


def layernorm(x: jax.Array, gamma: jax.Array, beta: jax.Array) -> jax.Array:
    x2, lead = _fold(x)
    y = _ln.layernorm(x2, gamma, beta, interpret=interpret_default())
    return y.reshape(lead + (x.shape[-1],))


def rmsnorm(x: jax.Array, gamma: jax.Array) -> jax.Array:
    x2, lead = _fold(x)
    y = _ln.rmsnorm(x2, gamma, interpret=interpret_default())
    return y.reshape(lead + (x.shape[-1],))


def quantized_dense(x: jax.Array, qw: QTensor) -> jax.Array:
    """Serving-path int8 dense: dynamic activation quant + int8 kernel."""
    x2, lead = _fold(x)
    qx = quantize_dynamic(x2)
    bm, bk, bn = _blocks(x2.shape[0], qw.values.shape[0],
                         qw.values.shape[1], dtype_bytes=1)
    y = _i8.int8_matmul(qx.values, qx.scale, qw.values, qw.scale,
                        bm=bm, bk=bk, bn=bn, interpret=interpret_default(),
                        out_dtype=x.dtype)
    return y.reshape(lead + (qw.values.shape[1],))
