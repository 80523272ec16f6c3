"""Error-feedback int8 gradient compression for the DP all-reduce.

Scheme (per gradient leaf, per step):
  1. reduce-scatter the f32 gradient (each device owns 1/n of the sum),
  2. add the local error-feedback residual, quantize the owned shard to
     int8 (per-shard symmetric scale), store the new residual,
  3. all-gather the int8 shards + scales and dequantize.

Wire bytes drop from ~8x size (f32 ring all-reduce) to ~4x + 1x, a ~38%
saving on the gradient-sync collective term, while error feedback keeps
the compression bias from accumulating (the residual re-enters the next
step, so the *time-averaged* update is unbiased — test-asserted).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


class EFState(NamedTuple):
    """Per-leaf error-feedback residual, shaped like the local grad shard."""

    residual: jax.Array


def init_ef_state(local_shard_shape: tuple[int, ...]) -> EFState:
    return EFState(jnp.zeros(local_shard_shape, jnp.float32))


def _quantize_shard(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_allreduce(g: jax.Array, ef: EFState, axis_name: str,
                         ) -> tuple[jax.Array, EFState]:
    """Mean-all-reduce of ``g`` over ``axis_name`` with int8 wire format.

    Must run inside shard_map.  Returns (mean gradient, new EF state).
    The EF residual has the shape of the local reduce-scatter shard
    (padded flat size / axis size).
    """
    n = lax.axis_size(axis_name)
    flat = g.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    owned = lax.psum_scatter(flat, axis_name, tiled=True) / n   # f32, 1/n
    owned = owned + ef.residual
    q, scale = _quantize_shard(owned)
    new_resid = owned - q.astype(jnp.float32) * scale
    q_all = lax.all_gather(q, axis_name, tiled=True)            # int8 wire
    s_all = lax.all_gather(scale.reshape(1), axis_name, tiled=True)  # [n]
    deq = q_all.astype(jnp.float32).reshape(n, -1) * s_all[:, None]
    out = deq.reshape(-1)[: g.size].reshape(g.shape)
    return out.astype(g.dtype), EFState(new_resid)


def compressed_allreduce_tree(grads, ef_tree, axis_name: str):
    """Apply ``compressed_allreduce`` leaf-wise over a gradient pytree."""
    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = tdef.flatten_up_to(ef_tree)
    outs, states = [], []
    for g, e in zip(flat_g, flat_e):
        o, s = compressed_allreduce(g, e, axis_name)
        outs.append(o)
        states.append(s)
    return jax.tree.unflatten(tdef, outs), jax.tree.unflatten(tdef, states)


def init_ef_tree(grads_abstract, n_devices: int):
    """EF state tree matching ``compressed_allreduce``'s shard shapes."""
    def one(leaf):
        flat = 1
        for d in leaf.shape:
            flat *= d
        shard = (flat + (-flat) % n_devices) // n_devices
        return init_ef_state((shard,))

    return jax.tree.map(one, grads_abstract)


def wire_bytes(n_params: int, n_devices: int, compressed: bool) -> int:
    """Per-device wire traffic of one gradient sync (reporting helper)."""
    if not compressed:
        return int(2 * (n_devices - 1) / n_devices * n_params * 4)
    rs = (n_devices - 1) / n_devices * n_params * 4   # f32 reduce-scatter
    ag = (n_devices - 1) / n_devices * n_params * 1   # int8 all-gather
    return int(rs + ag)
