"""Logical-axis sharding rules -> physical mesh shardings (DP/TP/EP/SP/FSDP).

The model zoo annotates every parameter with *logical* axis names
("embed", "heads", "ffn", "experts", ...) via ``ParamBuilder('axes')``.
This module translates those names to physical mesh axes under a
``ShardingStrategy`` and resolves per-leaf divisibility: a logical axis
whose dimension does not divide its mesh extent falls back to replication
for that leaf (e.g. 3 attention heads on a 16-way model axis), so *every*
architecture lowers on *every* mesh — the portability requirement the
paper demonstrates across U55C/VC707/ZCU102 (Fig. 11).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Iterator

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.paging import pool_row


@dataclasses.dataclass(frozen=True)
class ShardingStrategy:
    """Which parallelism features are active and on which mesh axes."""

    dp_axes: tuple[str, ...] = ("data",)   # batch / gradient all-reduce
    tp_axis: str | None = "model"          # tensor parallel (heads/ffn/vocab)
    fsdp: bool = False                     # shard 'embed' of params over dp
    sp: bool = False                       # sequence-parallel activations
    ep_axis: str | None = None             # experts; defaults to tp_axis

    @property
    def expert_axis(self) -> str | None:
        return self.ep_axis or self.tp_axis


def strategy_for_mesh(mesh: Mesh, **kw) -> ShardingStrategy:
    """Default strategy: every non-'model' mesh axis is data-parallel."""
    dp = tuple(a for a in mesh.axis_names if a != "model")
    tp = "model" if "model" in mesh.axis_names else None
    return ShardingStrategy(dp_axes=dp, tp_axis=tp, **kw)


# Logical axis name -> rule key.  Anything unlisted is replicated.
def param_rules(s: ShardingStrategy) -> dict[str, Any]:
    tp = s.tp_axis
    r: dict[str, Any] = {
        "vocab": tp, "heads": tp, "kv_heads": tp, "ffn": tp,
        "experts": s.expert_axis, "dinner": tp, "lru": tp,
        "embed": s.dp_axes if s.fsdp else None,
        "q_lora": None, "kv_lora": None,
        "layers": None, "pos": None, "state": None,
    }
    return r


def activation_rules(s: ShardingStrategy) -> dict[str, Any]:
    return {
        "batch": s.dp_axes,
        # Megatron-SP: between blocks the residual stream is token-sharded
        # over the TP axis, so the TP all-reduce decomposes into
        # reduce-scatter (+ bf16 all-gather at the next matmul)
        "seq": s.tp_axis if s.sp else None,
        "heads": s.tp_axis, "kv_heads": s.tp_axis, "ffn": s.tp_axis,
        "experts": s.expert_axis, "embed": None, "vocab": s.tp_axis,
        "dinner": s.tp_axis, "lru": s.tp_axis,
    }


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def resolve_spec(logical: P, shape: tuple[int, ...], rules: dict,
                 mesh: Mesh) -> P:
    """Translate a logical PartitionSpec to mesh axes with divisibility
    fallback; drops mesh axes already used by an earlier dim."""
    out = []
    used: set[str] = set()
    for dim, name in enumerate(tuple(logical) + (None,) * (len(shape) - len(logical))):
        axes = rules.get(name) if name is not None else None
        if axes is None:
            out.append(None)
            continue
        ax_tuple = (axes,) if isinstance(axes, str) else tuple(axes)
        ax_tuple = tuple(a for a in ax_tuple
                         if a in mesh.shape and a not in used)
        if not ax_tuple or shape[dim] % _axis_size(mesh, ax_tuple) != 0:
            out.append(None)
            continue
        used.update(ax_tuple)
        out.append(ax_tuple[0] if len(ax_tuple) == 1 else ax_tuple)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def tree_param_shardings(mesh: Mesh, axes_tree, abstract_tree,
                         strategy: ShardingStrategy):
    """Per-leaf NamedSharding for a parameter tree."""
    rules = param_rules(strategy)

    def one(spec, leaf):
        return NamedSharding(mesh, resolve_spec(spec, leaf.shape, rules, mesh))

    return jax.tree.map(one, axes_tree, abstract_tree,
                        is_leaf=lambda x: isinstance(x, P))


def tp_mesh(devices) -> Mesh:
    """A ``(data=1, model=len(devices))`` mesh over an explicit device
    list — one serving replica's tensor-parallel group.  The replica
    set's data parallelism lives on the host (``serving.cluster``), so
    the data axis is always 1 here; a single device yields a 1x1 mesh
    that pins every array to that device (how DP replicas get disjoint
    placements without a second code path)."""
    devs = list(devices)
    if not devs:
        raise ValueError("tp_mesh needs at least one device")
    return Mesh(np.asarray(devs).reshape(1, len(devs)), ("data", "model"))


def kv_cache_shardings(mesh: Mesh, cache, strategy: ShardingStrategy,
                       kv_heads: int | None = None,
                       head_dim: int | None = None):
    """Shardings for a decode-cache pytree, mirroring ``param_rules``'
    kv_heads rule with the same per-leaf divisibility fallback.

    KV caches are recognized structurally (a NamedTuple whose first two
    fields are ``k``/``v`` — ``models.attention.KVCache`` and the
    fabric's synthesis-time cache; importing them here would cycle):
    value leaves ``[..., n_kv, hd]`` shard the kv-head axis (-2) over
    the TP axis; given ``kv_heads``/``head_dim``, a paged pool whose
    rows merge a position's heads (``core.paging.pool_row``: ``[L, NB,
    bs, n_kv * hd]``) splits along that row (-1) when ``kv_heads``
    divides the TP extent; int8 scale rows (``[..., n_kv]``) shard their
    trailing kv-head axis, and everything else — MLA latents (no
    kv-head axis), recurrent state, hybrid per-layer entries that
    aren't attention — replicates.  A kv-head count that does not
    divide the TP extent replicates that leaf, so every arch lowers on
    every mesh."""
    tp = strategy.tp_axis
    tp_n = mesh.shape.get(tp, 1) if tp is not None else 1
    rep = NamedSharding(mesh, P())

    def values_spec(leaf) -> NamedSharding:
        heads = (kv_heads, head_dim)
        if kv_heads is None or leaf.shape[-2:] == heads \
                or leaf.shape[-1:] != pool_row(*heads):
            return axis_spec(leaf, -2)
        # a merged row splits only where whole heads land on each device
        return axis_spec(leaf, -1) if kv_heads % tp_n == 0 else rep

    def axis_spec(leaf, axis: int) -> NamedSharding:
        if tp_n > 1 and leaf.ndim > axis % leaf.ndim \
                and leaf.shape[axis] % tp_n == 0:
            # no trailing Nones: GSPMD canonicalizes specs that way, and a
            # non-canonical device_put sharding would miss the jit cache on
            # the call after the first (sharding is part of the C++ key)
            spec = [None] * (axis % leaf.ndim) + [tp]
            return NamedSharding(mesh, P(*spec))
        return rep

    def walk(node):
        if node is None:
            return None
        fields = getattr(node, "_fields", None)
        if fields is not None and fields[:2] == ("k", "v"):
            return type(node)(
                values_spec(node.k), values_spec(node.v),
                *(None if s is None else axis_spec(s, -1)
                  for s in node[2:]))
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)) and fields is None:
            return type(node)(walk(v) for v in node)
        # any other node (MLACache, stacked recurrent state, bare array)
        return jax.tree.map(lambda _: rep, node)

    return walk(cache)


def batch_sharding(mesh: Mesh, strategy: ShardingStrategy,
                   ndim: int = 2) -> NamedSharding:
    """Tokens/targets [B, S, ...]: batch over the dp axes."""
    dp = tuple(a for a in strategy.dp_axes if a in mesh.shape)
    spec = [dp if dp else None] + [None] * (ndim - 1)
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# In-graph activation constraints (GSPMD hints), context-scoped
# ---------------------------------------------------------------------------
_ctx = threading.local()


@contextlib.contextmanager
def active(mesh: Mesh, strategy: ShardingStrategy) -> Iterator[None]:
    old = getattr(_ctx, "state", None)
    _ctx.state = (mesh, strategy, activation_rules(strategy))
    try:
        yield
    finally:
        _ctx.state = old


def constrain(x: jax.Array, logical_axes: tuple[str | None, ...]) -> jax.Array:
    """with_sharding_constraint against the active rules; no-op outside
    an ``active(...)`` scope, off-mesh, or when every axis resolves to
    replicated (an explicit empty constraint would *force* replication
    and fight propagation — measured as a 10x memory regression on the
    qwen2 prefill cell)."""
    state = getattr(_ctx, "state", None)
    if state is None:
        return x
    mesh, _, rules = state
    spec = resolve_spec(P(*logical_axes), x.shape, rules, mesh)
    if not tuple(spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def per_device_bytes(tree, mesh: Mesh, shardings) -> int:
    """Static estimate of per-device bytes for a sharded tree."""
    total = 0
    for leaf, sh in zip(jax.tree.leaves(tree), jax.tree.leaves(
            shardings, is_leaf=lambda x: isinstance(x, NamedSharding))):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        shards = 1
        for axes in sh.spec:
            if axes is None:
                continue
            shards *= _axis_size(mesh, axes)
        total += n * leaf.dtype.itemsize // max(shards, 1)
    return total
