"""Weights made on the device from a seed, shared by every family.

The benchmark owns the weights: a family's ``make`` draws them in one
jitted call (``from_seed``), in the dtype they are served in, under
names of its own; its ``to_program`` only rearranges those arrays into
the serving program's parameter tree, and the plain reference reads the
names.  ``draw`` scales each kind: projections N(0, 1/fan_in),
embeddings and heads N(0, 0.05^2), norm gains 1 + N(0, 0.1^2), biases
N(0, 0.2^2), so every parameter shapes the logits.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EMBED_STD, GAIN_STD, BIAS_STD = 0.05, 0.1, 0.2
DTYPES = {"bf16": jnp.bfloat16, "fp32": jnp.float32}


def seed_key(seed: int) -> jax.Array:
    """A key from all 64 bits of ``seed`` (``PRNGKey`` keeps only 32)."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def draw(shapes: dict, key: jax.Array, dtype) -> dict:
    """Every weight of ``shapes`` (name -> (shape, kind)), the i-th name
    in sorted order from ``fold_in(key, i)`` (trace under ``jax.jit``)."""
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if kind == "embed":
            w = EMBED_STD * z
        elif kind == "gain":
            w = 1.0 + GAIN_STD * z
        elif kind == "bias":
            w = BIAS_STD * z
        else:
            w = z / math.sqrt(shape[-2])
        out[name] = w.astype(dtype)
    return out


def from_seed(family, cfg: dict, seed: int) -> dict:
    """The family's weights for ``cfg``, drawn on the device in one call."""
    dtype = DTYPES[cfg["serving"]["param_dtype"]]
    return jax.jit(lambda k: family.make(cfg, k, dtype))(seed_key(seed))


def check_tree(want, got) -> None:
    """Refuse a parameter tree that is not ``want``'s, leaf for leaf in
    shape and dtype (both abstract, ``jax.eval_shape``)."""
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise SystemExit("the benchmark's weights do not fit the program's "
                         "parameter tree")
