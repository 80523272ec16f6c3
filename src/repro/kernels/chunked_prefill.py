"""Chunked-prefill attention: block-table-aware chunk attention with
causal intra-chunk masking, fused over the paged KV pool.

``paged_attention.paged_decode_attention`` attends ONE query token per
sequence to its scattered pool blocks.  Chunked prefill generalizes the
query side: each slot advances by up to W consecutive *lanes* per fused
step (a prompt chunk, or a single decode token in lane 0), every lane
``l`` sitting at cache position ``start[b] + l``.  The chunk's K/V are
scattered into the pool *before* this kernel runs, so one mask covers
both halves of chunked attention: lane ``l`` sees pool positions
``<= start[b] + l`` — the prior cache plus the causal prefix of its own
chunk.

Grid: (seq, kv_head, lane, block).  Each program attends one lane's
query group (the n_rep query heads sharing a KV head) to one pool block,
accumulating the running (max, sum, acc) triple in VMEM scratch exactly
as in ``paged_attention``; the block table is scalar-prefetched and the
KV BlockSpec index map reads ``table[seq, j]``, so the non-contiguous
pool walk costs no gather in HBM.  Dead lanes (>= the slot's live count)
compute a finite garbage row that the caller drops — the idle-PE
discipline.

int8 KV cache: per-(block entry, kv-head) scales stream in beside the
int8 tiles through the same block-table index map and the dequant fuses
into the dots (see ``paged_attention`` for the layout).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _chunk_kernel(scale: float, bs: int, masked_heads: bool,
                  quantized: bool, *refs):
    refs = list(refs)
    bt_ref, start_ref = refs.pop(0), refs.pop(0)
    live_ref = refs.pop(0) if masked_heads else None
    q_ref, k_ref, v_ref = refs.pop(0), refs.pop(0), refs.pop(0)
    ks_ref = refs.pop(0) if quantized else None
    vs_ref = refs.pop(0) if quantized else None
    o_ref, acc, m_s, l_s = refs
    b = pl.program_id(0)
    g = pl.program_id(1)
    lane = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q = q_ref[0, 0, 0]                 # [R, hdp]  (one lane's query group)
    k = k_ref[0, 0]                    # [bs, hdp] (one pool block)
    v = v_ref[0, 0]
    if quantized:
        q = q.astype(jnp.float32)
        k = k.astype(jnp.float32)
        v = v.astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if quantized:
        # dequant fused at the tile: K's per-entry scale is a per-column
        # factor of the scores ([1, bs] row broadcast over the queries)
        s = s * ks_ref[0, 0]
    # chunk K/V are already in the pool, so the single causal-vs-cache
    # mask is: column position (logical block j * bs + offset) <= the
    # lane's own cache position start[b] + lane
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos <= start_ref[b] + lane, s, NEG_INF)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_s[...] = m_new
    # V's per-entry scale weights the probabilities of its column; the
    # softmax denominator above sums the unscaled probabilities
    pv = p * vs_ref[0, 0] if quantized else p
    acc[...] = acc[...] * alpha + jax.lax.dot_general(
        pv.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(3) - 1)
    def _flush():
        l = jnp.maximum(l_s[...], 1e-30)
        out = acc[...] / l
        if live_ref is not None:
            # multi-topology serving: KV-head groups >= this sequence's
            # live head count are padded fabric lanes — force the
            # idle-PE contract (exact zeros)
            out = jnp.where(g < live_ref[b], out, 0.0)
        o_ref[0, 0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def chunked_prefill_attention(q: jax.Array, k_pool: jax.Array,
                              v_pool: jax.Array, block_tables: jax.Array,
                              start: jax.Array, *,
                              live_kv: jax.Array | None = None,
                              k_scale: jax.Array | None = None,
                              v_scale: jax.Array | None = None,
                              scale: float | None = None,
                              interpret: bool = False) -> jax.Array:
    """W-lane chunk/decode attention over the pooled KV cache.

    q:            [B, W, h, hd]     W query lanes per sequence; lane l
                                    sits at cache position start[b] + l
    k/v_pool:     [NB, bs, kv, hd]  the shared block pool (row 0 = null)
    block_tables: [B, nblk] int32   physical block of each logical block
    start:        [B] int32         first lane's cache position per slot
    live_kv:      [B] int32 or None live KV-head groups per sequence
                                    (multi-topology head-lane masking)
    k/v_scale:    [NB, bs, kv] f32 or None — the int8 cache codec's
                  per-(block entry, kv-head) scales; when given, pool
                  values are int8 and the dequant fuses into the kernel
    -> [B, W, h, hd]

    Softmax statistics accumulate in f32 VMEM scratch; numerics match
    ``flash_attention``, not bit-exactly the unfused XLA softmax.
    """
    B, W, h, hd = q.shape
    nb_pool, bs, kv, _ = k_pool.shape
    nblk = block_tables.shape[1]
    n_rep = h // kv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    R = _rup(max(n_rep, 8), 8)
    hdp = _rup(hd, 128)
    # query groups: head = kv_head * n_rep + rep (repeat_kv's ordering),
    # laid out kv-major so one program streams one lane's group
    qg = q.reshape(B, W, kv, n_rep, hd).transpose(0, 2, 1, 3, 4)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, R - n_rep),
                      (0, hdp - hd)))
    kp = jnp.pad(k_pool, ((0, 0), (0, 0), (0, 0), (0, hdp - hd))) \
        .swapaxes(1, 2)
    vp = jnp.pad(v_pool, ((0, 0), (0, 0), (0, 0), (0, hdp - hd))) \
        .swapaxes(1, 2)

    masked_heads = live_kv is not None
    quantized = k_scale is not None
    # index maps take one trailing arg per scalar-prefetch operand
    if masked_heads:
        q_map = lambda b, g, l, j, bt, st, lv: (b, g, l, 0, 0)
        kv_map = lambda b, g, l, j, bt, st, lv: (bt[b, j], g, 0, 0)
        prefetch = (block_tables, start, live_kv)
    else:
        q_map = lambda b, g, l, j, bt, st: (b, g, l, 0, 0)
        kv_map = lambda b, g, l, j, bt, st: (bt[b, j], g, 0, 0)
        prefetch = (block_tables, start)
    in_specs = [
        pl.BlockSpec((1, 1, 1, R, hdp), q_map),
        pl.BlockSpec((1, 1, bs, hdp), kv_map),
        pl.BlockSpec((1, 1, bs, hdp), kv_map),
    ]
    operands = [qg, kp, vp]
    if quantized:
        # scales kv-major as [NB, kv, 1, bs]: a (1, bs) tile spans both
        # trailing dims whole, which Mosaic accepts at any block size, and
        # it rides the pool's own block-table index map
        in_specs += [pl.BlockSpec((1, 1, 1, bs), kv_map),
                     pl.BlockSpec((1, 1, 1, bs), kv_map)]
        operands += [k_scale.swapaxes(1, 2)[:, :, None],
                     v_scale.swapaxes(1, 2)[:, :, None]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, kv, W, nblk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, 1, R, hdp), q_map),
        scratch_shapes=[pltpu.VMEM((R, hdp), jnp.float32),
                        pltpu.VMEM((R, 1), jnp.float32),
                        pltpu.VMEM((R, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, scale, bs, masked_heads, quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kv, W, R, hdp),
                                       jnp.float32 if quantized else q.dtype),
        interpret=interpret,
        name="chunked_prefill_attention",
    )(*prefetch, *operands)
    return out[:, :, :, :n_rep, :hd].transpose(0, 2, 1, 3, 4) \
        .reshape(B, W, h, hd).astype(q.dtype)


def _rup(x: int, m: int) -> int:
    return -(-x // m) * m
