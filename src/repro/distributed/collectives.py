"""Collective schedules for shard_map regions (pipeline, compressed DP).

Under ``jit`` GSPMD chooses collective algorithms itself; these helpers
exist for the explicitly-scheduled ``shard_map`` paths where we control
the wire format — ring reduce-scatter/all-gather built from
``ppermute`` so each step moves 1/n of the buffer (overlap-friendly:
chunk k is on the wire while chunk k-1 is being reduced), and the
compressed variants used by ``distributed.compression``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _ring_perm(n: int) -> list[tuple[int, int]]:
    return [(i, (i + 1) % n) for i in range(n)]


def ring_reduce_scatter(x: jax.Array, axis_name: str) -> jax.Array:
    """Explicit ring reduce-scatter: [n*c] -> [c], device i ends with the
    full sum of chunk i.

    n-1 ppermute steps; at step s the partial resident on device i is for
    chunk (i + n-1-s) mod n, and the device folds in its own contribution
    for that chunk.  Each step moves 1/n of the buffer, so compute on the
    previous chunk can overlap the transfer of the next — the gradient
    analogue of the paper's load-weights-while-PEs-compute overlap.
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = lax.axis_index(axis_name)
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)

    def chunk_at(k):
        return jnp.take(chunks, k % n, axis=0)

    acc = chunk_at(idx + n - 1)
    for s in range(1, n):
        acc = lax.ppermute(acc, axis_name, _ring_perm(n))
        acc = acc + chunk_at(idx + n - 1 - s)
    return acc


def psum_scatter(x: jax.Array, axis_name: str) -> jax.Array:
    """Reduce-scatter via the native collective (lowering-friendly)."""
    return lax.psum_scatter(x, axis_name, tiled=True)


def all_gather(x: jax.Array, axis_name: str) -> jax.Array:
    return lax.all_gather(x, axis_name, tiled=True)


def ring_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """reduce-scatter + all-gather decomposition of all-reduce.

    Moves 2*(n-1)/n of the buffer per device instead of the naive
    n-fanout, and exposes the two phases separately so the caller can
    overlap them with compute (the paper's 'load weights while the PEs
    compute' discipline, §3.6.1, applied to gradients).
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    scattered = lax.psum_scatter(flat, axis_name, tiled=True)
    gathered = lax.all_gather(scattered, axis_name, tiled=True)
    return gathered[: x.size].reshape(x.shape)
