"""Microbatched pipeline parallelism via shard_map + ppermute (GPipe).

Stages live on a dedicated mesh axis; layer-stacked params are sharded
along it so each device holds one stage's weights.  The schedule runs
``n_micro + n_stages - 1`` ticks: every tick each stage applies its layer
to the activation it holds, then the activation ring-shifts one stage to
the right while the next microbatch enters stage 0.  The bubble fraction
is the classic (S-1)/(T+S-1); the launcher picks ``n_micro >= 4*stages``
to keep it under 6%.

``ppermute`` is differentiable, so ``jax.grad`` through
``pipeline_forward`` yields the reverse-schedule backward pass for free.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import Mesh, PartitionSpec as P


def _shift_right(x: jax.Array, axis_name: str) -> jax.Array:
    n = lax.axis_size(axis_name)
    return lax.ppermute(x, axis_name, [(i, (i + 1) % n) for i in range(n)])


def pipeline_forward(stage_fn: Callable, stage_params, x: jax.Array, *,
                     axis_name: str = "stage") -> jax.Array:
    """Inside-shard_map pipelined apply.

    stage_params: this device's stage weights (leading stage dim removed
    by shard_map).  x: [n_micro, mb, ...] microbatched input, replicated.
    Returns [n_micro, mb, ...] outputs of the *last* stage, replicated.
    """
    n_stages = lax.axis_size(axis_name)
    stage_idx = lax.axis_index(axis_name)
    n_micro = x.shape[0]
    total = n_micro + n_stages - 1
    # shard_map leaves a size-1 stage dim on every param leaf; drop it
    stage_params = jax.tree.map(lambda l: jnp.squeeze(l, 0), stage_params)

    state = jnp.zeros_like(x[0])                 # activation held by stage
    outputs = jnp.zeros_like(x)

    def tick(t, carry):
        state, outputs = carry
        # stage 0 ingests microbatch t (if any remain); others use held state
        mb = jnp.take(x, jnp.minimum(t, n_micro - 1), axis=0)
        inp = jnp.where(stage_idx == 0, mb, state)
        out = stage_fn(stage_params, inp)
        # last stage emits microbatch (t - (n_stages-1)) when it is valid
        emit_idx = t - (n_stages - 1)
        valid = (stage_idx == n_stages - 1) & (emit_idx >= 0)
        outputs = lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(valid, out, jnp.take(outputs, jnp.maximum(emit_idx, 0),
                                           axis=0)),
            jnp.maximum(emit_idx, 0), axis=0)
        state = _shift_right(out, axis_name)
        return state, outputs

    _, outputs = lax.fori_loop(0, total, tick, (state, outputs))
    # every device returns the outputs buffer; only the last stage's is
    # complete -> broadcast it around the ring so the result is replicated
    outputs = _shift_right(outputs, axis_name)   # last -> stage 0
    for _ in range(n_stages - 1):                # replicate to everyone
        nxt = _shift_right(outputs, axis_name)
        outputs = jnp.where(stage_idx == 0, outputs, nxt)
    return outputs


def make_pipelined_apply(stage_fn: Callable, mesh: Mesh, *,
                         axis_name: str = "stage",
                         param_spec: P | None = None) -> Callable:
    """Wrap ``stage_fn(stage_params, x) -> x`` into a mesh-level pipelined
    apply: f(stacked_params [S, ...], x [n_micro, mb, ...]) -> outputs."""
    pspec = param_spec if param_spec is not None else P(axis_name)

    fn = jax.shard_map(
        functools.partial(pipeline_forward, stage_fn, axis_name=axis_name),
        mesh=mesh,
        in_specs=(pspec, P()),   # pspec is a pytree-prefix for the params
        out_specs=P(),
        check_vma=False,
    )

    def apply(stacked_params, x):
        return fn(stacked_params, x)

    return apply


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """Idle fraction of the GPipe schedule (reported by the launcher)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
