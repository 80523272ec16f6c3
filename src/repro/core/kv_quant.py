"""The KV-cache codec: quantize-on-write / dequantize-on-read decode state.

ADAPTOR is "fully quantized for computational efficiency and portability"
(paper C6) — the FPGA keeps *all* resident state in fixed point, not just
the weight matrices.  The serving analogue: the KV cache is the binding
resource at high concurrency (cache bytes bound admitted requests long
before FLOPs do), so storing it at int8 instead of bf16 nearly doubles
concurrent capacity at equal HBM.

One ``CacheCodec`` policy object rules every cache layout:

* **compute** — values are stored in the compute dtype (bf16); the codec
  is the identity and no scale arrays exist.  Bit-identical to the
  historical behaviour.
* **int8**    — values are stored as symmetric int8 with one f32 scale
  per *cache row* (per (position, kv-head) for GQA K/V, per position for
  MLA latents), reduced over the trailing feature dim.  Write-local:
  quantizing a new token touches only its own row, so the fused decode
  step stays a pure scatter.  Scales live in arrays shaped like the
  values minus the feature dim and ride beside the dense rows or the
  paged pool (``[NB, bs, kv]`` for the pool — one scale per block entry
  per kv head), through the same block tables, inserts and donation.

``encode``/``decode`` are the only quantization math; ``store``/``load``
are the call-site helpers that collapse to a no-op in compute mode, so
every attention variant carries exactly one codec line per cache access.

Storage cost per cached feature row of width ``d``: ``d`` bytes of int8
values + 4 bytes of f32 scale, vs ``2 d`` bytes of bf16 — a
``2 d / (d + 4)`` compression (1.88x at head_dim 64, 1.94x at 128).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

KV_DTYPES = ("compute", "int8")

# Keeps a zero row's scale finite; any value quantizes to 0 against it.
_EPS = 1e-8


@dataclasses.dataclass(frozen=True)
class CacheCodec:
    """Frozen per-engine policy: how cache rows are stored and recovered.

    ``kv_dtype="compute"`` is the identity codec (no scales, no casts
    beyond the storage dtype); ``"int8"`` is symmetric per-row int8 with
    f32 scales reduced over the trailing feature dim.
    """

    kv_dtype: str = "compute"

    def __post_init__(self) -> None:
        if self.kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"CacheCodec.kv_dtype={self.kv_dtype!r} is not one of "
                f"{KV_DTYPES}")

    @property
    def quantized(self) -> bool:
        return self.kv_dtype == "int8"

    def storage_dtype(self, compute_dtype: Any = jnp.bfloat16):
        """dtype of the cache *values* arrays."""
        return jnp.int8 if self.quantized else compute_dtype

    # ------------------------------------------------------------------
    # The quantization math (int8 mode)
    # ------------------------------------------------------------------
    def encode(self, x: jax.Array) -> tuple[jax.Array, jax.Array]:
        """float ``[..., d]`` -> (int8 values ``[..., d]``, f32 scales
        ``[...]``), symmetric per-row: scale = amax(|row|) / 127."""
        x32 = x.astype(jnp.float32)
        amax = jnp.max(jnp.abs(x32), axis=-1)
        scale = jnp.maximum(amax, _EPS) / 127.0
        q = jnp.clip(jnp.round(x32 / scale[..., None]), -127, 127)
        return q.astype(jnp.int8), scale

    def decode(self, values: jax.Array, scale: jax.Array,
               dtype: Any = jnp.bfloat16) -> jax.Array:
        """int8 values + per-row scales -> float ``[..., d]``."""
        out = values.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
        return out.astype(dtype)

    # ------------------------------------------------------------------
    # Call-site helpers (identity in compute mode)
    # ------------------------------------------------------------------
    def store(self, x: jax.Array, store_dtype: Any
              ) -> tuple[jax.Array, jax.Array | None]:
        """Values (+ scales, or None) ready for the cache scatter."""
        if not self.quantized:
            return x.astype(store_dtype), None
        return self.encode(x)

    def load(self, values: jax.Array, scale: jax.Array | None,
             dtype: Any = jnp.bfloat16) -> jax.Array:
        """A float view of stored values (pass-through in compute mode)."""
        if not self.quantized:
            return values
        return self.decode(values, scale, dtype)

    # ------------------------------------------------------------------
    # Cache construction
    # ------------------------------------------------------------------
    def cache_arrays(self, shape: tuple[int, ...], *,
                     scale_shape: tuple[int, ...] | None = None,
                     compute_dtype: Any = jnp.bfloat16,
                     abstract: bool = False):
        """(values, scales-or-None) leaves for one cache tensor whose
        trailing dim is the quantized feature dim.  Scales are shaped
        ``scale_shape`` (default: the values minus that dim)."""
        vd = self.storage_dtype(compute_dtype)
        sshape = shape[:-1] if scale_shape is None else scale_shape
        if abstract:
            vals = jax.ShapeDtypeStruct(shape, vd)
            sc = jax.ShapeDtypeStruct(sshape, jnp.float32) \
                if self.quantized else None
        else:
            vals = jnp.zeros(shape, vd)
            sc = jnp.zeros(sshape, jnp.float32) if self.quantized else None
        return vals, sc

    def bytes_per_feature_row(self, d: int, compute_dtype: Any = jnp.bfloat16
                              ) -> int:
        """HBM bytes one cached row of width ``d`` costs (the
        memory-per-slot arithmetic used by capacity planning)."""
        if self.quantized:
            return d + 4                       # int8 values + f32 scale
        return d * jnp.dtype(compute_dtype).itemsize


FLOAT_CODEC = CacheCodec("compute")


def cache_put(values: jax.Array, scales: jax.Array | None, idx: tuple,
              new_vals: jax.Array, new_scales: jax.Array | None,
              layer: jax.Array | int | None = None
              ) -> tuple[jax.Array, jax.Array | None]:
    """Scatter codec-stored (values, scales) at ``idx`` — the one write
    primitive shared by every cache layout (dense rows, paged blocks,
    chunk lanes) and every attention variant; scales are None end-to-end
    in compute mode.  ``layer`` addresses one layer of a layer-stacked
    cache in place (it is prepended to ``idx``)."""
    if layer is not None:
        idx = (layer, *idx)
    out_v = values.at[idx].set(new_vals)
    out_s = scales if new_scales is None else scales.at[idx].set(new_scales)
    return out_v, out_s


def fork_block(cache, src: jax.Array, dst: jax.Array):
    """Copy-on-write fork: copy pool block ``src`` into block ``dst``
    across every leaf of a paged cache pytree.

    Every paged cache leaf — GQA K/V values, MLA latents, and their int8
    scale arrays alike — is pool-block-major on axis 1
    (``[layers, pool_blocks, block_size, ...]``), so one tree.map forks
    values *and* scales together: a shared block's ``(position, kv-head)``
    scale rows are duplicated with its int8 rows and the fork stays
    exactly the codec's stored representation (bit-identical readback).
    ``src``/``dst`` may be traced scalars; the caller jits this with the
    cache donated so XLA rewrites the two rows in place.
    """
    return jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), cache)


def gather_view(codec: CacheCodec, values: jax.Array,
                scales: jax.Array | None, block_tables: jax.Array,
                shape: tuple[int, ...], dtype,
                layer: jax.Array | int | None = None) -> jax.Array:
    """Block-table gather of a pooled cache into sequence-major ``shape``,
    dequantized on the way out (the fused-on-TPU read half of the
    codec).  ``layer`` reads one layer of a layer-stacked pool: one
    gather at ``(layer, block_tables)``, no per-layer pool array."""
    idx = block_tables if layer is None else (layer, block_tables)
    g = values[idx].reshape(shape)
    if not codec.quantized:
        return g
    sg = scales[idx].reshape(shape[:-1])
    return codec.decode(g, sg, dtype)


def layer_view(a: jax.Array | None, layer: jax.Array | int
               ) -> jax.Array | None:
    """One layer of a layer-stacked cache leaf; an absent leaf (a
    compute-mode scale) stays None."""
    return None if a is None else a[layer]
