"""Serving-time weight quantization (paper C6 applied to deployment).

Walks a parameter tree and replaces eligible leaves — 2-D+ matmul kernels
(path key 'kernel') and embedding/unembedding tables ('table') — with
int8 ``QTensor``s: per-output-channel scales for kernels, per-row scales
for tables.  Three parallel entry points mirror ``ParamBuilder``'s modes:

* ``quantize_params``   — real arrays (runnable serving),
* ``quantize_abstract`` — ShapeDtypeStructs (dry-run lowering),
* ``quantize_axes``     — PartitionSpecs (sharding trees).

All three produce structurally identical trees, so the existing
``tree_param_shardings`` machinery works unchanged.  The eligibility
floor defaults to ``core.quant.DEFAULT_QUANT_MIN_SIZE`` and is
configured per engine through ``ExecutionSpec.quant_min_size``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.quant import DEFAULT_QUANT_MIN_SIZE, QTensor


def _last_key(path) -> str:
    for pp in reversed(path):
        if hasattr(pp, "key"):
            return str(pp.key)
    return ""


def _eligible(path, leaf, min_size: int = DEFAULT_QUANT_MIN_SIZE) -> str | None:
    """Returns 'kernel' / 'table' when the leaf should be quantized."""
    name = _last_key(path)
    if name not in ("kernel", "table"):
        return None
    shape = getattr(leaf, "shape", None)
    if shape is None or len(shape) < 2:
        return None
    n = 1
    for d in shape:
        n *= d
    if n < min_size:
        return None
    return name


def _map_with_path(tree, fn):
    flat = jax.tree_util.tree_flatten_with_path(tree)
    leaves = [fn(path, leaf) for path, leaf in flat[0]]
    return jax.tree_util.tree_unflatten(flat[1], leaves)


def quantize_leaf(leaf, kind: str) -> QTensor:
    """Kernels [..., K, N]: per-(stack, column) scales reducing over the
    contraction dim only; tables [V, ...]: per-row scales."""
    w = leaf.astype(jnp.float32)
    axis = -2 if kind == "kernel" else tuple(range(1, w.ndim))
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return QTensor(q, scale)


# one compiled program per leaf shape: the float32 view of a leaf is
# fused into its reductions, never held whole beside the weights
_quantize_leaf = jax.jit(quantize_leaf, static_argnums=1)


def quantize_params(params, min_size: int = DEFAULT_QUANT_MIN_SIZE):
    """Real arrays -> int8 QTensors (kernels per-column, tables per-row)."""

    def one(path, leaf):
        kind = _eligible(path, leaf, min_size)
        if kind is None:
            return leaf
        return _quantize_leaf(leaf, kind)

    return _map_with_path(params, one)


def quantize_abstract(abstract, min_size: int = DEFAULT_QUANT_MIN_SIZE):
    """ShapeDtypeStruct tree -> QTensor(SDS int8, SDS f32 scale)."""

    def one(path, leaf):
        kind = _eligible(path, leaf, min_size)
        if kind is None:
            return leaf
        shape = leaf.shape
        if kind == "kernel":  # keep stack dims, collapse the contraction dim
            sshape = shape[:-2] + (1, shape[-1])
        else:
            sshape = (shape[0],) + tuple(1 for _ in shape[1:])
        return QTensor(jax.ShapeDtypeStruct(shape, jnp.int8),
                       jax.ShapeDtypeStruct(sshape, jnp.float32))

    return _map_with_path(abstract, one)


def quantize_axes(axes, abstract, min_size: int = DEFAULT_QUANT_MIN_SIZE):
    """Logical-axes tree -> QTensor(P values, P scale) matching
    ``quantize_abstract``'s structure.  The scale inherits the spec of its
    non-degenerate dim so it co-shards with the values."""
    flat_ax = jax.tree_util.tree_flatten_with_path(
        axes, is_leaf=lambda x: isinstance(x, P))
    flat_ab = jax.tree_util.tree_flatten_with_path(abstract)
    leaves = []
    for (path, spec), (_, leaf) in zip(flat_ax[0], flat_ab[0]):
        kind = _eligible(path, leaf, min_size)
        if kind is None:
            leaves.append(spec)
            continue
        names = tuple(spec) + (None,) * (len(leaf.shape) - len(spec))
        if kind == "kernel":  # [stack..., 1, N] scale co-shards with values
            sspec = P(*(names[:-2] + (None, names[-1])))
        else:
            sspec = P(*((names[0],) + (None,) * (len(leaf.shape) - 1)))
        leaves.append(QTensor(spec, sspec))
    return jax.tree_util.tree_unflatten(flat_ax[1], leaves)
