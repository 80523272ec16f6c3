"""Attention variants: GQA (full / blockwise / local-window) and MLA.

The paper's QK_PM -> softmax -> SV_PM pipeline (§3.6) appears here in three
forms:

* ``full_attention``       — direct einsum chain, used for short sequences;
  this is the literal Algorithm 11/7/12 composition.
* ``blockwise_attention``  — query-block streamed attention with the score
  rows never exceeding one block: the TPU analogue of the paper's tiled
  BRAM reuse (scores stay "on chip" per tile).  Used for long sequences on
  the XLA path; the Pallas ``flash_attention`` kernel is the TPU-native
  fusion of the same pipeline.
* ``local_attention``      — banded window attention (RecurrentGemma).

MLA (DeepSeek-V3) keeps the paper's dense-matmul discipline: every
projection routes through ``layers.dense`` and is therefore tiled by the
same machinery.  Decode uses the *absorbed* formulation so the per-step
cost scales with the latent width, not the expanded head dims.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import masking
from repro.core.kv_quant import (FLOAT_CODEC, CacheCodec, cache_put,
                                 gather_view, layer_view)
from repro.core.paging import NULL_BLOCK
from repro.distributed.sharding import constrain
from repro.kernels.runtime import interpret_default
from repro.models import layers
from repro.models.layers import apply_rope, build_dense, apply_dense

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

# Sequences at or above this length use blockwise (streamed) attention on
# the XLA path; below it the direct einsum chain is cheaper to compile.
BLOCKWISE_THRESHOLD = 8_192
QUERY_BLOCK = 1_024


class KVCache(NamedTuple):
    """Decode-time K/V cache for one attention stack.

    Two layouts share this pytree (the cache-layout interface):

    * dense — ``[B, S_max, n_kv, hd]``: one preallocated row per slot.
    * paged — ``[num_blocks, block_size, n_kv, hd]``: a pooled cache of
      fixed-size token blocks; a slot's sequence is scattered across the
      pool and addressed through its block table (``core.paging``).
      Where ``hd`` is not a multiple of the TPU's 128 lanes a position's
      heads share one row, ``[num_blocks, block_size, n_kv * hd]``
      (``core.paging.pool_row``).  The decode and mixed steps hand every
      paged attention layer the whole layer-stacked pool (``[L, ...]``
      leaves) and its ``layer`` index: each layer writes and reads its
      own rows in place.

    Two storage codecs share it too (``core.kv_quant.CacheCodec``):
    under ``kv_dtype="int8"`` the ``k``/``v`` values are int8 and the
    ``k_scale``/``v_scale`` arrays (values shape minus the trailing
    head_dim — one f32 scale per (position, kv-head) row) ride beside
    them through the same scatters, gathers and block tables; in
    ``"compute"`` mode the scale fields are None and the pytree is
    structurally the historical (k, v) pair.
    """

    k: jax.Array
    v: jax.Array
    k_scale: jax.Array | None = None
    v_scale: jax.Array | None = None


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[B, S, kv, hd] -> [B, S, kv*n_rep, hd] (GQA head grouping)."""
    if n_rep == 1:
        return x
    b, s, kv, hd = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, kv, n_rep, hd)) \
        .reshape(b, s, kv * n_rep, hd)


def _causal_mask(q_len: int, kv_len: int, q_offset) -> jax.Array:
    """[q_len, kv_len] bool; q position i (global i+q_offset) sees kv <= it."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    kv_pos = jnp.arange(kv_len)[None, :]
    return kv_pos <= q_pos


def full_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True, q_offset=0,
                   kv_len_mask: jax.Array | None = None,
                   scale: float | None = None) -> jax.Array:
    """q: [B,Sq,h,hd], k/v: [B,Skv,kv,hd] (kv already repeated to h)."""
    b, sq, h, hd = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        s = jnp.where(_causal_mask(sq, k.shape[1], q_offset)[None, None], s, NEG_INF)
    if kv_len_mask is not None:
        # [B, Skv] live-position mask (decode / padding), or a per-lane
        # [B, Sq, Skv] mask (the chunked mixed step's causal-vs-cache view)
        m = kv_len_mask[:, None, None, :] if kv_len_mask.ndim == 2 \
            else kv_len_mask[:, None, :, :]
        s = jnp.where(m, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        query_block: int = QUERY_BLOCK,
                        scale: float | None = None) -> jax.Array:
    """Query-block streamed attention: peak score memory B*h*Qb*Skv.

    XLA-level flash attention — the same tiling Fig. 4 applies to weight
    matrices, applied to the score matrix.  Exact (not approximate).
    """
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    vd = v.shape[-1]  # MLA: value head dim differs from qk head dim
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    nb = -(-sq // query_block)
    pad = nb * query_block - sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = q.reshape(b, nb, query_block, h, hd).transpose(1, 0, 2, 3, 4)

    kv_pos = jnp.arange(skv)

    def one_block(carry, inp):
        qi, block_idx = inp
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k).astype(jnp.float32) * scale
        if causal:
            q_pos = block_idx * query_block + jnp.arange(query_block)
            m = kv_pos[None, :] <= q_pos[:, None]
            s = jnp.where(m[None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
        return carry, o

    _, ob = jax.lax.scan(one_block, None, (qb, jnp.arange(nb)))
    out = ob.transpose(1, 0, 2, 3, 4).reshape(b, nb * query_block, h, vd)
    return out[:, :sq]


def local_attention(q: jax.Array, k: jax.Array, v: jax.Array, window: int, *,
                    scale: float | None = None) -> jax.Array:
    """Causal banded attention: position i attends to (i-window, i].

    Implemented block-wise (block = window): each query block attends to its
    own and the previous key block, so memory is B*h*S*2W, never S^2.
    """
    b, s, h, hd = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    w = min(window, s)
    nb = -(-s // w)
    pad = nb * w - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = nb * w
    qb = q.reshape(b, nb, w, h, hd)
    kb = k.reshape(b, nb, w, h, hd)
    vb = v.reshape(b, nb, w, h, hd)
    # keys for block i: blocks (i-1, i); block -1 is zeros and fully masked.
    k_prev = jnp.concatenate([jnp.zeros_like(kb[:, :1]), kb[:, :-1]], axis=1)
    v_prev = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]], axis=1)
    k2 = jnp.concatenate([k_prev, kb], axis=2)  # [b, nb, 2w, h, hd]
    v2 = jnp.concatenate([v_prev, vb], axis=2)
    sc = jnp.einsum("bnqhd,bnkhd->bnhqk", qb, k2).astype(jnp.float32) * scale
    q_pos = jnp.arange(w)[:, None] + w                    # within the 2w frame
    kv_pos = jnp.arange(2 * w)[None, :]
    m = (kv_pos <= q_pos) & (kv_pos > q_pos - w)          # (i-w, i]
    first = (jnp.arange(nb) == 0)[:, None, None]          # block -1 is invalid
    m = m[None] & (~first | (kv_pos[None] >= w))
    sc = jnp.where(m[:, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    ob = jnp.einsum("bnhqk,bnkhd->bnqhd", p.astype(v2.dtype), v2)
    return ob.reshape(b, sp, h, hd)[:, :s]


# ---------------------------------------------------------------------------
# GQA attention block (params + apply)
# ---------------------------------------------------------------------------
def build_gqa(b, cfg: ArchConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "wq": build_dense(b, d, h * hd, ("embed", "heads"), use_bias=cfg.qkv_bias),
        "wk": build_dense(b, d, kv * hd, ("embed", "kv_heads"), use_bias=cfg.qkv_bias),
        "wv": build_dense(b, d, kv * hd, ("embed", "kv_heads"), use_bias=cfg.qkv_bias),
        "wo": build_dense(b, h * hd, d, ("heads", "embed")),
    }


def gqa_qkv(x: jax.Array, p: dict, cfg: ArchConfig, positions: jax.Array,
            rope: bool = True) -> tuple[jax.Array, jax.Array, jax.Array]:
    b_, s, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = apply_dense(x, p["wq"]).reshape(b_, s, h, hd)
    k = apply_dense(x, p["wk"]).reshape(b_, s, kv, hd)
    v = apply_dense(x, p["wv"]).reshape(b_, s, kv, hd)
    if rope and cfg.positional == "rope":
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    q = constrain(q, ("batch", None, "heads", None))
    k = constrain(k, ("batch", None, "kv_heads", None))
    v = constrain(v, ("batch", None, "kv_heads", None))
    return q, k, v


def gqa_attention(x: jax.Array, p: dict, cfg: ArchConfig, *,
                  positions: jax.Array, causal: bool = True,
                  window: int | None = None) -> jax.Array:
    """Full-sequence (train / prefill) GQA attention."""
    b_, s, _ = x.shape
    q, k, v = gqa_qkv(x, p, cfg, positions)
    n_rep = cfg.num_heads // max(cfg.num_kv_heads, 1)
    k, v = repeat_kv(k, n_rep), repeat_kv(v, n_rep)
    if window is not None and s > window:
        o = local_attention(q, k, v, window)
    elif s >= BLOCKWISE_THRESHOLD:
        o = blockwise_attention(q, k, v, causal=causal)
    else:
        o = full_attention(q, k, v, causal=causal)
    o = o.reshape(b_, s, cfg.num_heads * cfg.resolved_head_dim)
    return apply_dense(o, p["wo"])


def gqa_prefill(x: jax.Array, p: dict, cfg: ArchConfig, *,
                positions: jax.Array, max_len: int,
                window: int | None = None,
                causal: bool = True,
                codec: CacheCodec | None = None
                ) -> tuple[jax.Array, KVCache]:
    """Full-sequence attention that also emits this layer's decode cache."""
    codec = codec or FLOAT_CODEC
    b_, s, _ = x.shape
    q, k, v = gqa_qkv(x, p, cfg, positions)
    n_rep = cfg.num_heads // max(cfg.num_kv_heads, 1)
    kf, vf = repeat_kv(k, n_rep), repeat_kv(v, n_rep)
    if window is not None and s > window:
        o = local_attention(q, kf, vf, window)
    elif s >= BLOCKWISE_THRESHOLD:
        o = blockwise_attention(q, kf, vf, causal=causal)
    else:
        o = full_attention(q, kf, vf, causal=causal)
    o = apply_dense(o.reshape(b_, s, cfg.num_heads * cfg.resolved_head_dim),
                    p["wo"])
    if window is not None:
        # rolling buffer: row (p % window) holds token p, for the last W
        # tokens (hybrid family — never quantized, see spec validation)
        if codec.quantized:
            raise ValueError("kv_dtype='int8' is unsupported for "
                             "rolling-window attention caches")
        w = min(window, max_len)
        start = max(s - w, 0)
        rows = (jnp.arange(start, start + w) % w) if s >= w else jnp.arange(w)
        src = k[:, start:start + w], v[:, start:start + w]
        ck = jnp.zeros((b_, w) + k.shape[2:], jnp.bfloat16)
        cv = jnp.zeros_like(ck)
        n_src = src[0].shape[1]
        ck = ck.at[:, rows[:n_src]].set(src[0].astype(jnp.bfloat16))
        cv = cv.at[:, rows[:n_src]].set(src[1].astype(jnp.bfloat16))
        return o, KVCache(ck, cv)
    pad = ((0, 0), (0, max_len - s), (0, 0), (0, 0))
    kq, ks = codec.store(k, jnp.bfloat16)
    vq, vs = codec.store(v, jnp.bfloat16)
    if ks is None:
        return o, KVCache(jnp.pad(kq, pad), jnp.pad(vq, pad))
    return o, KVCache(jnp.pad(kq, pad), jnp.pad(vq, pad),
                      jnp.pad(ks, pad[:-1]), jnp.pad(vs, pad[:-1]))


def mla_prefill(x: jax.Array, p: dict, cfg: ArchConfig, *,
                positions: jax.Array, max_len: int,
                codec: CacheCodec | None = None
                ) -> tuple[jax.Array, MLACache]:
    """MLA prefill: attention output + this layer's latent cache."""
    codec = codec or FLOAT_CODEC
    b_, s, _ = x.shape
    o = mla_attention(x, p, cfg, positions=positions)
    c_kv, k_rope = _mla_latent(x, p, cfg, positions)
    pad = ((0, 0), (0, max_len - s), (0, 0))
    cq, cs = codec.store(c_kv, jnp.bfloat16)
    rq, rs = codec.store(k_rope, jnp.bfloat16)
    if cs is None:
        return o, MLACache(jnp.pad(cq, pad), jnp.pad(rq, pad))
    return o, MLACache(jnp.pad(cq, pad), jnp.pad(rq, pad),
                       jnp.pad(cs, pad[:-1]), jnp.pad(rs, pad[:-1]))


def as_index_vector(cache_index: jax.Array, batch: int) -> jax.Array:
    """Scalar or [B] cache index -> [B] int32 (per-slot decode support)."""
    idx = jnp.asarray(cache_index, jnp.int32)
    if idx.ndim == 0:
        idx = jnp.broadcast_to(idx, (batch,))
    return idx


def _gqa_attend(q: jax.Array, k: jax.Array, v: jax.Array, live: jax.Array,
                cfg: ArchConfig, grouped: bool) -> jax.Array:
    """Decode/chunk score/value contraction over a [B, S, kv, hd] view.

    ``live`` is [B, S] (one query lane per slot) or [B, W, S] (the mixed
    step's per-lane causal-vs-cache masks).  Shared by the dense and
    paged layouts: both reduce to the same masked attention once the
    cache has been (gathered into) sequence-major form, which is what
    keeps the two layouts bit-identical.
    """
    b_, nq = q.shape[:2]
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    n_rep = h // max(kv, 1)
    if grouped:
        # GQA-grouped contraction: the KV cache is used directly, never
        # materialized at h heads (repeat_kv costs ~2x cache bytes/layer)
        lv = live[:, None, :] if live.ndim == 2 else live      # [B, W, S]
        qg = q.reshape(b_, nq, kv, n_rep, hd)
        s = jnp.einsum("bqkrd,bskd->bkrqs", qg, k).astype(jnp.float32)
        s = s / math.sqrt(hd)
        s = jnp.where(lv[:, None, None, :, :], s, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkrqs,bskd->bqkrd", pr.astype(v.dtype), v)
        return o.reshape(b_, nq, h * hd)
    kf, vf = repeat_kv(k, n_rep), repeat_kv(v, n_rep)
    o = full_attention(q, kf, vf, causal=False, kv_len_mask=live)
    return o.reshape(b_, nq, h * hd)


def gqa_decode(x: jax.Array, p: dict, cfg: ArchConfig, cache: KVCache,
               cache_index: jax.Array, *,
               window: int | None = None,
               grouped: bool = False,
               codec: CacheCodec | None = None) -> tuple[jax.Array, KVCache]:
    """One-token decode against a [B, S_max, kv, hd] cache.

    ``cache_index`` is the number of tokens already in the cache — a
    scalar, or a [B] vector for per-slot serving (continuous batching).
    For windowed layers the cache is a rolling buffer of size window.
    ``grouped``: GQA-grouped score contraction (no repeat_kv copy).
    ``codec``: the cache codec; int8 quantizes the new token's K/V row on
    write and fuses the dequant into the attend.
    """
    codec = codec or FLOAT_CODEC
    b_, one, _ = x.shape
    idx_vec = as_index_vector(cache_index, b_)
    positions = idx_vec[:, None]
    q, k_new, v_new = gqa_qkv(x, p, cfg, positions)
    s_max = cache.k.shape[1]
    slot = idx_vec % s_max if window is not None else idx_vec
    rows = jnp.arange(b_)
    kq, ks = codec.store(k_new[:, 0], cache.k.dtype)
    vq, vs = codec.store(v_new[:, 0], cache.v.dtype)
    k, k_sc = cache_put(cache.k, cache.k_scale, (rows, slot), kq, ks)
    v, v_sc = cache_put(cache.v, cache.v_scale, (rows, slot), vq, vs)
    idx = jnp.arange(s_max)
    if window is not None:  # rolling-buffer validity, per slot
        live = (idx[None, :] <= slot[:, None]) | (idx_vec[:, None] >= s_max)
    else:
        live = idx[None, :] <= idx_vec[:, None]
    o = _gqa_attend(q, codec.load(k, k_sc, x.dtype),
                    codec.load(v, v_sc, x.dtype), live, cfg, grouped)
    return apply_dense(o, p["wo"]), KVCache(k, v, k_sc, v_sc)


def _pool_rows(x: jax.Array, pool: jax.Array) -> jax.Array:
    """New K/V rows ``[..., kv, hd]`` in the paged pool's row shape."""
    return x.reshape(*x.shape[:-2], *pool.shape[3:])


def paged_write_slot(idx_vec: jax.Array, block_tables: jax.Array,
                     block_size: int) -> tuple[jax.Array, jax.Array]:
    """(physical block, in-block offset) for each slot's next cache write.

    ``idx_vec`` is [B] (one write per slot) or [B, W] (the mixed step's
    chunk lanes).  An index past the addressable range (cache full, slot
    finished but not yet harvested, dead chunk lane) is routed to the
    null block, so the fused step stays safe with zero host intervention.
    """
    t_max = block_tables.shape[1] * block_size
    safe = jnp.minimum(idx_vec, t_max - 1)
    if idx_vec.ndim == 1:
        blk = jnp.take_along_axis(block_tables, (safe // block_size)[:, None],
                                  axis=1)[:, 0]
    else:
        blk = jnp.take_along_axis(block_tables, safe // block_size, axis=1)
    blk = jnp.where(idx_vec < t_max, blk, NULL_BLOCK)
    return blk, safe % block_size


def gqa_decode_paged(x: jax.Array, p: dict, cfg: ArchConfig, cache: KVCache,
                     cache_index: jax.Array, block_tables: jax.Array,
                     layer: jax.Array | int, *,
                     grouped: bool = False,
                     impl: str = "gather",
                     codec: CacheCodec | None = None
                     ) -> tuple[jax.Array, KVCache]:
    """One-token decode against layer ``layer`` of the layer-stacked pool
    [L, NB, bs, ...]: one scatter at (layer, block, offset) writes the
    new row in place and one gather at (layer, block_tables) reads the
    slot's view, so no per-layer pool array is formed.

    ``block_tables``: [B, blocks_per_slot] int32 — logical block i of a
    slot lives in pool row ``block_tables[slot, i]`` (0 = null block).
    ``impl``: "gather" (XLA gather + the dense contraction, bit-identical
    to the dense layout) or "pallas" (the fused paged-decode kernel).
    With an int8 codec the per-(block entry, kv-head) scales ride the
    same block tables: gathered beside the values on the XLA path, walked
    by the same scalar-prefetched index maps inside the Pallas kernel.
    """
    codec = codec or FLOAT_CODEC
    b_, one, _ = x.shape
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    bs = cache.k.shape[2]
    idx_vec = as_index_vector(cache_index, b_)
    with jax.named_scope("attn.qkv"):
        q, k_new, v_new = gqa_qkv(x, p, cfg, idx_vec[:, None])
    with jax.named_scope("attn.kv_write"):
        blk, off = paged_write_slot(idx_vec, block_tables, bs)
        kq, ks = codec.store(k_new[:, 0], cache.k.dtype)
        vq, vs = codec.store(v_new[:, 0], cache.v.dtype)
        k, k_sc = cache_put(cache.k, cache.k_scale, (blk, off),
                            _pool_rows(kq, cache.k), ks, layer)
        v, v_sc = cache_put(cache.v, cache.v_scale, (blk, off),
                            _pool_rows(vq, cache.v), vs, layer)
    t_max = block_tables.shape[1] * bs
    if impl == "pallas":
        from repro.kernels.paged_attention import paged_decode_attention
        with jax.named_scope("attn.core"):
            lengths = jnp.minimum(idx_vec + 1, t_max)
            pool = (*k.shape[1:3], kv, hd)
            o = paged_decode_attention(
                q[:, 0], k[layer].reshape(pool), v[layer].reshape(pool),
                block_tables, lengths,
                k_scale=layer_view(k_sc, layer),
                v_scale=layer_view(v_sc, layer),
                interpret=interpret_default())
            o = o.reshape(b_, one, cfg.num_heads * hd)
    else:
        with jax.named_scope("attn.kv_gather"):
            kg = gather_view(codec, k, k_sc, block_tables,
                             (b_, t_max, kv, hd), x.dtype, layer)
            vg = gather_view(codec, v, v_sc, block_tables,
                             (b_, t_max, kv, hd), x.dtype, layer)
        with jax.named_scope("attn.core"):
            live = jnp.arange(t_max)[None, :] <= idx_vec[:, None]
            o = _gqa_attend(q, kg, vg, live, cfg, grouped)
    with jax.named_scope("attn.out"):
        o = apply_dense(o, p["wo"])
    return o, KVCache(k, v, k_sc, v_sc)


# ---------------------------------------------------------------------------
# Mixed chunk/decode step — chunked prefill fused with decode
# ---------------------------------------------------------------------------
def gqa_mixed(x: jax.Array, p: dict, cfg: ArchConfig, cache: KVCache,
              start: jax.Array, n_live: jax.Array, *,
              grouped: bool = False,
              codec: CacheCodec | None = None) -> tuple[jax.Array, KVCache]:
    """W-lane chunk/decode attention against the dense [B, S_max] cache.

    ``x`` is [B, W, d]: lane ``l`` of slot ``b`` sits at cache position
    ``start[b] + l``; only the first ``n_live[b]`` lanes are real (a
    decoding slot uses one, a prefilling slot up to a chunk, an idle slot
    none).  Chunk K/V are written *before* the attend, so one
    causal-vs-cache mask covers intra-chunk causality and the prior
    cache — the math reduces exactly to ``gqa_decode`` at W == 1, and
    replaying a prompt chunk-by-chunk reproduces ``gqa_prefill``'s
    logits bit-for-bit below ``BLOCKWISE_THRESHOLD`` (above it bucketed
    prefill switches to the streaming softmax, whose accumulation order
    this unfused path does not mirror).
    """
    codec = codec or FLOAT_CODEC
    b_, w, _ = x.shape
    positions = start[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    q, k_new, v_new = gqa_qkv(x, p, cfg, positions)
    s_max = cache.k.shape[1]
    # dead lanes scatter out of bounds; jax drops those updates, so no
    # lane ever collides with a live write
    pos = jnp.where(masking.lane_mask(w, n_live), positions, s_max)
    rows = jnp.arange(b_)[:, None]
    kq, ks = codec.store(k_new, cache.k.dtype)
    vq, vs = codec.store(v_new, cache.v.dtype)
    k, k_sc = cache_put(cache.k, cache.k_scale, (rows, pos), kq, ks)
    v, v_sc = cache_put(cache.v, cache.v_scale, (rows, pos), vq, vs)
    live = masking.chunk_causal_mask(s_max, start, w)
    o = _gqa_attend(q, codec.load(k, k_sc, x.dtype),
                    codec.load(v, v_sc, x.dtype), live, cfg, grouped)
    return apply_dense(o, p["wo"]), KVCache(k, v, k_sc, v_sc)


def gqa_mixed_paged(x: jax.Array, p: dict, cfg: ArchConfig, cache: KVCache,
                    start: jax.Array, n_live: jax.Array,
                    block_tables: jax.Array, layer: jax.Array | int, *,
                    grouped: bool = False,
                    impl: str = "gather",
                    interpret: bool | None = None,
                    codec: CacheCodec | None = None
                    ) -> tuple[jax.Array, KVCache]:
    """W-lane chunk/decode attention against layer ``layer`` of the
    layer-stacked block pool (written and gathered in place, as in
    ``gqa_decode_paged``).

    ``impl="gather"`` materializes the block-table view and reuses the
    dense contraction (bit-identical to ``gqa_mixed``); ``"pallas"``
    streams pool blocks through the fused chunked-prefill kernel (the
    int8 codec's scales ride its scalar-prefetched block-table walk).
    """
    codec = codec or FLOAT_CODEC
    b_, w, _ = x.shape
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    bs = cache.k.shape[2]
    positions = start[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    with jax.named_scope("attn.qkv"):
        q, k_new, v_new = gqa_qkv(x, p, cfg, positions)
    t_max = block_tables.shape[1] * bs
    with jax.named_scope("attn.kv_write"):
        # dead lanes -> index t_max -> the null block absorbs them
        idx_w = jnp.where(masking.lane_mask(w, n_live), positions, t_max)
        blk, off = paged_write_slot(idx_w, block_tables, bs)
        kq, ks = codec.store(k_new, cache.k.dtype)
        vq, vs = codec.store(v_new, cache.v.dtype)
        k, k_sc = cache_put(cache.k, cache.k_scale, (blk, off),
                            _pool_rows(kq, cache.k), ks, layer)
        v, v_sc = cache_put(cache.v, cache.v_scale, (blk, off),
                            _pool_rows(vq, cache.v), vs, layer)
    if impl == "pallas":
        from repro.kernels.chunked_prefill import chunked_prefill_attention
        if interpret is None:
            interpret = interpret_default()
        with jax.named_scope("attn.core"):
            pool = (*k.shape[1:3], kv, hd)
            o = chunked_prefill_attention(q, k[layer].reshape(pool),
                                          v[layer].reshape(pool),
                                          block_tables, start,
                                          k_scale=layer_view(k_sc, layer),
                                          v_scale=layer_view(v_sc, layer),
                                          interpret=interpret)
            o = o.reshape(b_, w, cfg.num_heads * hd)
    else:
        with jax.named_scope("attn.kv_gather"):
            kg = gather_view(codec, k, k_sc, block_tables,
                             (b_, t_max, kv, hd), x.dtype, layer)
            vg = gather_view(codec, v, v_sc, block_tables,
                             (b_, t_max, kv, hd), x.dtype, layer)
        with jax.named_scope("attn.core"):
            live = masking.chunk_causal_mask(t_max, start, w)
            o = _gqa_attend(q, kg, vg, live, cfg, grouped)
    with jax.named_scope("attn.out"):
        o = apply_dense(o, p["wo"])
    return o, KVCache(k, v, k_sc, v_sc)


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek-V3)
# ---------------------------------------------------------------------------
class MLACache(NamedTuple):
    """Latent cache: the compressed kv + shared rope key (paper-faithful
    MLA).  Under the int8 codec the values are int8 and one f32 scale per
    cached position rides in ``c_scale``/``r_scale`` (None in compute
    mode — see ``KVCache``)."""

    c_kv: jax.Array    # [B, S_max, kv_lora]
    k_rope: jax.Array  # [B, S_max, rope_dim]
    c_scale: jax.Array | None = None
    r_scale: jax.Array | None = None


def build_mla(b, cfg: ArchConfig) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    return {
        "q_down": build_dense(b, d, m.q_lora_rank, ("embed", "q_lora")),
        "q_norm": {"scale": b.param((m.q_lora_rank,), ("q_lora",), init="ones")},
        "q_up": build_dense(b, m.q_lora_rank, h * m.qk_head_dim, ("q_lora", "heads")),
        "kv_down": build_dense(b, d, m.kv_lora_rank + m.qk_rope_head_dim,
                               ("embed", "kv_lora")),
        "kv_norm": {"scale": b.param((m.kv_lora_rank,), ("kv_lora",), init="ones")},
        "k_up": build_dense(b, m.kv_lora_rank, h * m.qk_nope_head_dim,
                            ("kv_lora", "heads")),
        "v_up": build_dense(b, m.kv_lora_rank, h * m.v_head_dim,
                            ("kv_lora", "heads")),
        "wo": build_dense(b, h * m.v_head_dim, d, ("heads", "embed")),
    }


def _mla_q(x, p, cfg: ArchConfig, positions):
    m, h = cfg.mla, cfg.num_heads
    b_, s, _ = x.shape
    cq = layers.rmsnorm(apply_dense(x, p["q_down"]), p["q_norm"]["scale"])
    q = apply_dense(cq, p["q_up"]).reshape(b_, s, h, m.qk_head_dim)
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    return q_nope, q_rope


def _mla_latent(x, p, cfg: ArchConfig, positions):
    m = cfg.mla
    b_, s, _ = x.shape
    ckv_full = apply_dense(x, p["kv_down"])
    c_kv = layers.rmsnorm(ckv_full[..., : m.kv_lora_rank], p["kv_norm"]["scale"])
    k_rope = ckv_full[..., m.kv_lora_rank:].reshape(b_, s, 1, m.qk_rope_head_dim)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta,
                        cfg.rope_scaling)[:, :, 0]
    return c_kv, k_rope


def mla_score_divisor(cfg: ArchConfig) -> float:
    """Scores are divided by this: sqrt of the q/k head dim, over YaRN's
    mscale(factor, mscale_all_dim) squared where the rope is scaled
    (exactly sqrt(qk_head_dim) without scaling)."""
    rs = cfg.rope_scaling
    m2 = 1.0 if rs is None or not rs.mscale_all_dim \
        else layers.yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return math.sqrt(cfg.mla.qk_head_dim) / m2


def mla_attention(x: jax.Array, p: dict, cfg: ArchConfig, *,
                  positions: jax.Array) -> jax.Array:
    """Train/prefill MLA: expand latents to per-head K/V (naive path)."""
    m, h = cfg.mla, cfg.num_heads
    b_, s, _ = x.shape
    q_nope, q_rope = _mla_q(x, p, cfg, positions)
    c_kv, k_rope = _mla_latent(x, p, cfg, positions)
    k_nope = apply_dense(c_kv, p["k_up"]).reshape(b_, s, h, m.qk_nope_head_dim)
    v = apply_dense(c_kv, p["v_up"]).reshape(b_, s, h, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, :, None], (b_, s, h, m.qk_rope_head_dim))], axis=-1)
    scale = 1.0 / mla_score_divisor(cfg)
    if s >= BLOCKWISE_THRESHOLD:
        o = blockwise_attention(q, k, v, causal=True, scale=scale)
    else:
        o = full_attention(q, k, v, causal=True, scale=scale)
    return apply_dense(o.reshape(b_, s, h * m.v_head_dim), p["wo"])


def _mla_attend(x: jax.Array, p: dict, cfg: ArchConfig, q_nope: jax.Array,
                q_rope: jax.Array, c_kv: jax.Array, k_rope: jax.Array,
                live: jax.Array) -> jax.Array:
    """Absorbed-matmul contraction over a sequence-major latent view
    (c_kv [B, S, rank], k_rope [B, S, rope_dim]) — shared by both cache
    layouts, which is what keeps dense and paged decode bit-identical."""
    m, h = cfg.mla, cfg.num_heads
    b_, one = q_nope.shape[:2]
    # the int8 weight path keeps these kernels quantized: read them whole
    wk = layers.maybe_dequant(p["k_up"]["kernel"], jnp.float32).reshape(
        m.kv_lora_rank, h, m.qk_nope_head_dim)
    # absorb k_up into the query: q_lat [B,1,h,kv_lora] (f32: one token only)
    q_lat = jnp.einsum("bqhd,lhd->bqhl", q_nope.astype(jnp.float32), wk)
    s_lat = jnp.einsum("bqhl,bkl->bhqk", q_lat, c_kv.astype(jnp.float32))
    s_rope = jnp.einsum("bqhd,bkd->bhqk", q_rope.astype(jnp.float32),
                        k_rope.astype(jnp.float32))
    scores = (s_lat + s_rope) / mla_score_divisor(cfg)
    scores = jnp.where(live, scores, NEG_INF)
    pr = jax.nn.softmax(scores, axis=-1)
    # attend in latent space, then expand once per step via v_up
    o_lat = jnp.einsum("bhqk,bkl->bqhl", pr.astype(c_kv.dtype), c_kv)
    wv = jnp.transpose(layers.maybe_dequant(p["v_up"]["kernel"], x.dtype)
                       .reshape(m.kv_lora_rank, h, m.v_head_dim), (1, 0, 2))
    o = jnp.einsum("bqhl,hld->bqhd", o_lat, wv)
    with jax.named_scope("attn.out"):
        return apply_dense(o.reshape(b_, one, h * m.v_head_dim), p["wo"])


def mla_decode(x: jax.Array, p: dict, cfg: ArchConfig, cache: MLACache,
               cache_index: jax.Array,
               codec: CacheCodec | None = None) -> tuple[jax.Array, MLACache]:
    """Absorbed-matmul MLA decode: score and value contraction happen in the
    latent space, so per-step FLOPs/bytes scale with kv_lora_rank."""
    codec = codec or FLOAT_CODEC
    b_, one, _ = x.shape
    idx_vec = as_index_vector(cache_index, b_)
    positions = idx_vec[:, None]
    q_nope, q_rope = _mla_q(x, p, cfg, positions)
    c_new, kr_new = _mla_latent(x, p, cfg, positions)
    rows = jnp.arange(b_)
    cq, cs = codec.store(c_new[:, 0], cache.c_kv.dtype)
    rq, rs = codec.store(kr_new[:, 0], cache.k_rope.dtype)
    c_kv, c_sc = cache_put(cache.c_kv, cache.c_scale, (rows, idx_vec),
                            cq, cs)
    k_rope, r_sc = cache_put(cache.k_rope, cache.r_scale, (rows, idx_vec),
                              rq, rs)
    s_max = c_kv.shape[1]
    live = (jnp.arange(s_max)[None] <= idx_vec[:, None])[:, None, None, :]
    out = _mla_attend(x, p, cfg, q_nope, q_rope,
                      codec.load(c_kv, c_sc, x.dtype),
                      codec.load(k_rope, r_sc, x.dtype), live)
    return out, MLACache(c_kv, k_rope, c_sc, r_sc)


def mla_decode_paged(x: jax.Array, p: dict, cfg: ArchConfig, cache: MLACache,
                     cache_index: jax.Array, block_tables: jax.Array,
                     layer: jax.Array | int,
                     codec: CacheCodec | None = None
                     ) -> tuple[jax.Array, MLACache]:
    """MLA decode against layer ``layer`` of the pooled latent blocks
    ([L, NB, bs, rank] c_kv and [L, NB, bs, rope_dim] k_rope addressed
    through the same block tables, in place)."""
    codec = codec or FLOAT_CODEC
    m = cfg.mla
    b_, one, _ = x.shape
    bs = cache.c_kv.shape[2]
    idx_vec = as_index_vector(cache_index, b_)
    positions = idx_vec[:, None]
    with jax.named_scope("attn.qkv"):
        q_nope, q_rope = _mla_q(x, p, cfg, positions)
        c_new, kr_new = _mla_latent(x, p, cfg, positions)
    with jax.named_scope("attn.kv_write"):
        blk, off = paged_write_slot(idx_vec, block_tables, bs)
        cq, cs = codec.store(c_new[:, 0], cache.c_kv.dtype)
        rq, rs = codec.store(kr_new[:, 0], cache.k_rope.dtype)
        c_kv, c_sc = cache_put(cache.c_kv, cache.c_scale, (blk, off),
                               cq, cs, layer)
        k_rope, r_sc = cache_put(cache.k_rope, cache.r_scale, (blk, off),
                                 rq, rs, layer)
    t_max = block_tables.shape[1] * bs
    with jax.named_scope("attn.kv_gather"):
        ckv_g = gather_view(codec, c_kv, c_sc, block_tables,
                            (b_, t_max, m.kv_lora_rank), x.dtype, layer)
        kr_g = gather_view(codec, k_rope, r_sc, block_tables,
                           (b_, t_max, m.qk_rope_head_dim), x.dtype, layer)
    with jax.named_scope("attn.core"):   # attn.out inside
        live = (jnp.arange(t_max)[None] <= idx_vec[:, None])[:, None, None, :]
        out = _mla_attend(x, p, cfg, q_nope, q_rope, ckv_g, kr_g, live)
    return out, MLACache(c_kv, k_rope, c_sc, r_sc)


def mla_mixed(x: jax.Array, p: dict, cfg: ArchConfig, cache: MLACache,
              start: jax.Array, n_live: jax.Array,
              codec: CacheCodec | None = None
              ) -> tuple[jax.Array, MLACache]:
    """W-lane chunk/decode MLA against the dense latent cache (absorbed
    contraction; see ``gqa_mixed`` for the lane protocol)."""
    codec = codec or FLOAT_CODEC
    b_, w, _ = x.shape
    positions = start[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    q_nope, q_rope = _mla_q(x, p, cfg, positions)
    c_new, kr_new = _mla_latent(x, p, cfg, positions)
    s_max = cache.c_kv.shape[1]
    pos = jnp.where(masking.lane_mask(w, n_live), positions, s_max)
    rows = jnp.arange(b_)[:, None]
    cq, cs = codec.store(c_new, cache.c_kv.dtype)
    rq, rs = codec.store(kr_new, cache.k_rope.dtype)
    c_kv, c_sc = cache_put(cache.c_kv, cache.c_scale, (rows, pos), cq, cs)
    k_rope, r_sc = cache_put(cache.k_rope, cache.r_scale, (rows, pos),
                              rq, rs)
    live = masking.chunk_causal_mask(s_max, start, w)[:, None]  # [B,1,W,S]
    out = _mla_attend(x, p, cfg, q_nope, q_rope,
                      codec.load(c_kv, c_sc, x.dtype),
                      codec.load(k_rope, r_sc, x.dtype), live)
    return out, MLACache(c_kv, k_rope, c_sc, r_sc)


def mla_mixed_paged(x: jax.Array, p: dict, cfg: ArchConfig, cache: MLACache,
                    start: jax.Array, n_live: jax.Array,
                    block_tables: jax.Array, layer: jax.Array | int,
                    codec: CacheCodec | None = None
                    ) -> tuple[jax.Array, MLACache]:
    """W-lane chunk/decode MLA against layer ``layer`` of the pooled
    latent block cache (in place)."""
    codec = codec or FLOAT_CODEC
    m = cfg.mla
    b_, w, _ = x.shape
    bs = cache.c_kv.shape[2]
    positions = start[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    with jax.named_scope("attn.qkv"):
        q_nope, q_rope = _mla_q(x, p, cfg, positions)
        c_new, kr_new = _mla_latent(x, p, cfg, positions)
    t_max = block_tables.shape[1] * bs
    with jax.named_scope("attn.kv_write"):
        idx_w = jnp.where(masking.lane_mask(w, n_live), positions, t_max)
        blk, off = paged_write_slot(idx_w, block_tables, bs)
        cq, cs = codec.store(c_new, cache.c_kv.dtype)
        rq, rs = codec.store(kr_new, cache.k_rope.dtype)
        c_kv, c_sc = cache_put(cache.c_kv, cache.c_scale, (blk, off),
                               cq, cs, layer)
        k_rope, r_sc = cache_put(cache.k_rope, cache.r_scale, (blk, off),
                                 rq, rs, layer)
    with jax.named_scope("attn.kv_gather"):
        ckv_g = gather_view(codec, c_kv, c_sc, block_tables,
                            (b_, t_max, m.kv_lora_rank), x.dtype, layer)
        kr_g = gather_view(codec, k_rope, r_sc, block_tables,
                           (b_, t_max, m.qk_rope_head_dim), x.dtype, layer)
    with jax.named_scope("attn.core"):   # attn.out inside
        live = masking.chunk_causal_mask(t_max, start, w)[:, None]
        out = _mla_attend(x, p, cfg, q_nope, q_rope, ckv_g, kr_g, live)
    return out, MLACache(c_kv, k_rope, c_sc, r_sc)
