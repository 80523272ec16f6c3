"""Absorbed latent attention (MLA) over each slot's live key blocks.

With ``k_up`` absorbed into the query, DeepSeek-V3's attention runs in the
latent space.  A query *row* is one (lane, head) pair of a slot; lane
``l`` of slot ``b`` sits at cache position ``start[b] + l``.  For row
``r`` and key position ``t``

    s[r, t] = (q_lat[r] . c_kv[t] + q_rope[r] . k_rope[t]) * scale

is masked to ``t <= start[b] + lane(r)``, softmaxed over ``t``, and the
row's output is ``sum_t p[r, t] c_kv[t]`` (the caller expands it through
``v_up``).

Grid: (slot, row tile, key block).  Each program scores one row tile
against one block of the slot's sequence-major latent view and folds it
into the running (max, sum, [rows, rank] accumulator) triple in VMEM
scratch, flash attention's online softmax: the f32 scores never reach
HBM.  Each slot's ``start`` is scalar-prefetched.  Key blocks past the
slot's last lane (``start[b] + W - 1``) are skipped, and their index map
repeats the last live block, so they are never fetched either: the work
and the bytes follow each slot's own length, not the view's.

MXU operands are the view's dtype (bf16 on the chip), with f32
accumulation; scores and softmax are f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

# keys per grid step, and query rows per program: a 512 x 512 f32 score
# tile and a [512, rank] f32 accumulator keep VMEM well inside its budget
KEY_BLOCK = 512
ROW_TILE = 512


def key_block(t_max: int) -> int:
    """Key positions per grid step for a view of ``t_max`` positions."""
    return min(KEY_BLOCK, t_max)


def live_keys(start: int, width: int, t_max: int) -> int:
    """Key positions in the blocks the kernel reads for a slot whose first
    lane sits at ``start`` with ``width`` lanes: every block up to the
    last lane's position, capped at the view."""
    tk = key_block(t_max)
    last = min(start + width - 1, t_max - 1)
    return min((last // tk + 1) * tk, t_max)


def _last_block(start, width: int, t_max: int, tk: int):
    """Index of the key block that holds a slot's last lane's position."""
    return jnp.minimum(start + width - 1, t_max - 1) // tk


def _kernel(scale: float, heads: int, width: int, t_max: int,
            start_ref, q_ref, qr_ref, c_ref, kr_ref, o_ref, acc, m_s, l_s):
    b, t, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    tq, tk = q_ref.shape[1], c_ref.shape[1]
    start = start_ref[b]
    n_live = _last_block(start, width, t_max, tk) + 1

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    @pl.when(k < n_live)
    def _block():
        c = c_ref[0]                                        # [tk, rank]
        nt = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(q_ref[0], c, nt,
                                preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(qr_ref[0], kr_ref[0], nt,
                                    preferred_element_type=jnp.float32)
        s = s * scale
        row = t * tq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        key = k * tk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        pos = start + jax.lax.div(row, heads)
        if t_max % tk:          # zero rows pad the view to whole blocks
            pos = jnp.minimum(pos, t_max - 1)
        s = jnp.where(key <= pos, s, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_s[...] = m_new
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == n_live - 1)
    def _flush():
        o_ref[0] = (acc[...] / l_s[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def latent_attention(q_lat: jax.Array, q_rope: jax.Array, c_kv: jax.Array,
                     k_rope: jax.Array, start: jax.Array, *, scale: float,
                     interpret: bool = False) -> jax.Array:
    """Causal latent attention of W query lanes per slot.

    q_lat:  [B, W, h, rank]    absorbed queries (``k_up`` folded in)
    q_rope: [B, W, h, rope]    rotary query parts
    c_kv:   [B, t_max, rank]   each slot's latent view, sequence-major
    k_rope: [B, t_max, rope]   its rotary keys
    start:  [B] int32          lane 0's cache position per slot
    -> [B, W, h, rank] in ``c_kv.dtype``

    Lane ``l`` sees positions ``<= start[b] + l`` of the view.
    """
    B, W, h, rank = q_lat.shape
    rope = q_rope.shape[-1]
    t_max = c_kv.shape[1]
    rows = W * h
    tq = min(ROW_TILE, rows)
    tk = key_block(t_max)
    pad_r, pad_k = -rows % tq, -t_max % tk
    q = jnp.pad(q_lat.reshape(B, rows, rank).astype(c_kv.dtype),
                ((0, 0), (0, pad_r), (0, 0)))
    qr = jnp.pad(q_rope.reshape(B, rows, rope).astype(k_rope.dtype),
                 ((0, 0), (0, pad_r), (0, 0)))
    if pad_k:
        c_kv = jnp.pad(c_kv, ((0, 0), (0, pad_k), (0, 0)))
        k_rope = jnp.pad(k_rope, ((0, 0), (0, pad_k), (0, 0)))

    def q_map(b, t, k, st):
        return b, t, 0

    def kv_map(b, t, k, st):
        return b, jnp.minimum(k, _last_block(st[b], W, t_max, tk)), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, (rows + pad_r) // tq, (t_max + pad_k) // tk),
        in_specs=[pl.BlockSpec((1, tq, rank), q_map),
                  pl.BlockSpec((1, tq, rope), q_map),
                  pl.BlockSpec((1, tk, rank), kv_map),
                  pl.BlockSpec((1, tk, rope), kv_map)],
        out_specs=pl.BlockSpec((1, tq, rank), q_map),
        scratch_shapes=[pltpu.VMEM((tq, rank), jnp.float32),
                        pltpu.VMEM((tq, 1), jnp.float32),
                        pltpu.VMEM((tq, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale, h, W, t_max),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows + pad_r, rank), c_kv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mla_attention",
    )(start.astype(jnp.int32), q, qr, c_kv, k_rope)
    return out[:, :rows].reshape(B, W, h, rank)
