"""Paged flash-decode attention: block-table gather fused into the
online-softmax loop.

The ``flash_attention`` kernel tiles a *contiguous* KV sequence; serving
with a paged cache makes the sequence non-contiguous — a slot's tokens
live in scattered pool blocks addressed by its block table.  The XLA
reference path (``models.attention.gqa_decode_paged`` impl="gather")
first materializes the contiguous view in HBM and then attends; this
kernel removes that copy by letting the *grid itself* walk the block
table: the tables are scalar-prefetched (SMEM), and the KV BlockSpec
index map reads ``table[seq, j]`` to DMA pool block ``j`` of each
sequence straight into VMEM — the ADAPTOR discipline of computing
addresses in registers while tiles stream through on-chip memory.

Grid: (seq, kv_head, block).  Each program attends one sequence's query
group (the n_rep query heads sharing a KV head) to one token block,
accumulating the running (max, sum, acc) triple in VMEM scratch exactly
as in ``flash_attention``; entries past the slot's live length — and
whole blocks whose table entry is the null block — are masked to -inf,
so they contribute exactly zero.

int8 KV cache (``MemorySpec.kv_dtype="int8"``): the per-(block entry,
kv-head) scales ride the *same* block-table index map as the values —
one f32 scale row per pool block per head streams into VMEM beside its
int8 tile and the dequant multiply fuses into the score/value dots, so
the quantized pool never takes a round trip through HBM at float width.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _paged_kernel(scale: float, bs: int, masked_heads: bool,
                  quantized: bool, *refs):
    refs = list(refs)
    bt_ref, len_ref = refs.pop(0), refs.pop(0)
    live_ref = refs.pop(0) if masked_heads else None
    q_ref, k_ref, v_ref = refs.pop(0), refs.pop(0), refs.pop(0)
    ks_ref = refs.pop(0) if quantized else None
    vs_ref = refs.pop(0) if quantized else None
    o_ref, acc, m_s, l_s = refs
    b = pl.program_id(0)
    g = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q = q_ref[0, 0]                    # [R, hdp]  (query group)
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
    # token position of each column = logical block j * bs + offset; the
    # block table already routed us to the right *physical* block, so
    # only the live-length mask remains (null-block columns are >= len)
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < len_ref[b], s, NEG_INF)

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

    @pl.when(j == pl.num_programs(2) - 1)
    def _flush():
        l = jnp.maximum(l_s[...], 1e-30)
        out = acc[...] / l
        if live_ref is not None:
            # multi-topology serving: KV-head groups >= this sequence's
            # live head count are padded fabric lanes — their q/k/v may
            # hold garbage, so force the idle-PE contract (exact zeros)
            out = jnp.where(g < live_ref[b], out, 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, *,
                           live_kv: jax.Array | None = None,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           scale: float | None = None,
                           interpret: bool = False) -> jax.Array:
    """One-token decode attention over the pooled KV cache.

    q:            [B, h, hd]        one query token per sequence
    k/v_pool:     [NB, bs, kv, hd]  the shared block pool (row 0 = null)
    block_tables: [B, nblk] int32   physical block of each logical block
    lengths:      [B] int32         live positions per sequence (index+1)
    live_kv:      [B] int32 or None live KV-head groups per sequence —
                  multi-topology serving pads the head axis to the fabric
                  maxima, and groups past a slot's live count are masked
                  to exact zeros (idle PE lanes)
    k/v_scale:    [NB, bs, kv] f32 or None — the int8 cache codec's
                  per-(block entry, kv-head) scales; when given, pool
                  values are int8 and the dequant fuses into the kernel,
                  the scales walking the same block-table index map
    -> [B, h, hd]

    Softmax statistics accumulate in f32 VMEM scratch; numerics match
    ``flash_attention``, not bit-exactly the unfused XLA softmax.
    """
    B, h, hd = q.shape
    nb_pool, bs, kv, _ = k_pool.shape
    nblk = block_tables.shape[1]
    n_rep = h // kv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    R = _rup(max(n_rep, 8), 8)
    hdp = _rup(hd, 128)
    # query groups: head = kv_head * n_rep + rep (repeat_kv's ordering)
    qg = q.reshape(B, kv, n_rep, hd)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, R - n_rep), (0, hdp - hd)))
    # kv-major pool view [NB, kv, bs, hdp]: the (bs, hdp) block trailing
    # dims are lane/sublane aligned.  On TPU a production pool would be
    # stored in this layout outright; the interpret-mode validation pays
    # the transpose here.
    kp = jnp.pad(k_pool, ((0, 0), (0, 0), (0, 0), (0, hdp - hd))) \
        .swapaxes(1, 2)
    vp = jnp.pad(v_pool, ((0, 0), (0, 0), (0, 0), (0, hdp - hd))) \
        .swapaxes(1, 2)

    masked_heads = live_kv is not None
    quantized = k_scale is not None
    # index maps take one trailing arg per scalar-prefetch operand
    if masked_heads:
        q_map = lambda b, g, j, bt, ln, lv: (b, g, 0, 0)
        kv_map = lambda b, g, j, bt, ln, lv: (bt[b, j], g, 0, 0)
        prefetch = (block_tables, lengths, live_kv)
    else:
        q_map = lambda b, g, j, bt, ln: (b, g, 0, 0)
        kv_map = lambda b, g, j, bt, ln: (bt[b, j], g, 0, 0)
        prefetch = (block_tables, lengths)
    in_specs = [
        pl.BlockSpec((1, 1, R, hdp), q_map),
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
        grid=(B, kv, nblk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, R, hdp), q_map),
        scratch_shapes=[pltpu.VMEM((R, hdp), jnp.float32),
                        pltpu.VMEM((R, 1), jnp.float32),
                        pltpu.VMEM((R, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale, bs, masked_heads, quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kv, R, hdp),
                                       jnp.float32 if quantized else q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(*prefetch, *operands)
    return out[:, :, :n_rep, :hd].reshape(B, h, hd).astype(q.dtype)


def _rup(x: int, m: int) -> int:
    return -(-x // m) * m
