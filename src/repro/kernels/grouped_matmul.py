"""Grouped matmul over expert-sorted rows: the MoE layer's expert FFNs.

``grouped_matmul(rows, w, sizes)`` computes, for each expert ``g``, the
``sizes[g]`` rows that follow the previous experts' times ``w[g]``, with
the Pallas grouped matmul of ``jax.experimental.pallas.ops.tpu.megablox``.
Its grid visits only the (row tile, expert) pairs that hold rows, so an
expert with no row is not read at all and the matmul streams just the
experts hit.  Rows past the last group are left unwritten; the caller
discards them.  The kernel's ``op_name`` keeps the named scope around
it (``moe.experts``), where XLA's own expansion of ``jax.lax.ragged_dot``
on the chip names its ops ``ragged-dot-*`` and nothing else.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.runtime import interpret_default

# row, contraction and output tiles: a 512 x 1024 bf16 weight tile (1 MiB)
# per grid step keeps the weight stream long; a decode step's rows
# (slots x experts per token) fill one 128-row tile
TILE_M, TILE_K, TILE_N = 128, 512, 1024


def grouped_matmul(rows: jax.Array, w: jax.Array, sizes: jax.Array,
                   layer: jax.Array | int | None = None,
                   interpret: bool | None = None) -> jax.Array:
    """rows [m, k] sorted by group, w [g, k, n], sizes [g] int32 ->
    [m, n] in ``rows.dtype``; rows past ``sizes.sum()`` are unspecified.

    With ``layer``, ``w`` is a stack [L, g, k, n] and the groups are
    layer ``layer``'s: the kernel reads them in place, where a slice of
    the stack handed to it would be copied out whole first.
    ``interpret`` defaults to interpret mode off the TPU."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    if interpret is None:
        interpret = interpret_default()
    if layer is not None:
        n_layers, g = w.shape[:2]
        w = w.reshape(n_layers * g, *w.shape[2:])
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_layers * g,), jnp.int32), sizes, (layer * g,))
    m, k = rows.shape
    tm = min(TILE_M, -(-m // 8) * 8)
    pad = -m % tm
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = gmm(rows, w.astype(rows.dtype), sizes,
              preferred_element_type=rows.dtype,
              tiling=(tm, min(TILE_K, k), min(TILE_N, w.shape[-1])),
              interpret=interpret)
    return out[:m]
