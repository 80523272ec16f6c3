"""Weights of a Qwen2-style dense decoder, made on the device from a seed.

The benchmark owns the weights: ``make`` draws them in one jitted call,
in the dtype they are served in, under names of its own; ``to_program``
only rearranges those arrays into the serving program's parameter tree,
and the plain reference reads ``make``'s names.  Scales: projections
N(0, 1/fan_in), embeddings and the untied head N(0, 0.05^2), norm gains
1 + N(0, 0.1^2), q/k/v biases N(0, 0.2^2), so every parameter shapes
the logits.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.flops import dims

EMBED_STD, GAIN_STD, BIAS_STD = 0.05, 0.1, 0.2
DTYPES = {"bf16": jnp.bfloat16, "fp32": jnp.float32}


def seed_key(seed: int) -> jax.Array:
    """A key from all 64 bits of ``seed`` (``PRNGKey`` keeps only 32)."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def shapes(cfg: dict) -> dict:
    """name -> (shape, kind) of every weight, layers stacked first."""
    m = dims(cfg)
    d, h, kv, hd, ff, n = m["d"], m["h"], m["kv"], m["hd"], m["ff"], \
        m["layers"]
    out = {"embed": ((m["vocab"], d), "embed"),
           "final_norm": ((d,), "gain"),
           "ln1": ((n, d), "gain"), "ln2": ((n, d), "gain"),
           "wq": ((n, d, h * hd), "proj"), "bq": ((n, h * hd), "bias"),
           "wk": ((n, d, kv * hd), "proj"), "bk": ((n, kv * hd), "bias"),
           "wv": ((n, d, kv * hd), "proj"), "bv": ((n, kv * hd), "bias"),
           "wo": ((n, h * hd, d), "proj"),
           "w_gate": ((n, d, ff), "proj"), "w_up": ((n, d, ff), "proj"),
           "w_down": ((n, ff, d), "proj")}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = ((m["vocab"], d), "embed")
    return out


def make(cfg: dict, key: jax.Array, dtype) -> dict:
    """Every weight, drawn from ``key`` (trace under ``jax.jit``)."""
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(shapes(cfg).items())):
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


def to_program(cfg: dict, w: dict) -> dict:
    """The serving program's parameter tree over the same arrays."""
    tree = {"embed": {"table": w["embed"]},
            "final_norm": {"scale": w["final_norm"]},
            "layers": {
                "ln1": {"scale": w["ln1"]},
                "attn": {"wq": {"kernel": w["wq"], "bias": w["bq"]},
                         "wk": {"kernel": w["wk"], "bias": w["bk"]},
                         "wv": {"kernel": w["wv"], "bias": w["bv"]},
                         "wo": {"kernel": w["wo"]}},
                "ln2": {"scale": w["ln2"]},
                "ffn": {"w1": {"kernel": w["w_up"]},
                        "wg": {"kernel": w["w_gate"]},
                        "w2": {"kernel": w["w_down"]}}}}
    if "lm_head" in w:
        tree["lm_head"] = {"table": w["lm_head"]}
    return tree


def weight_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    return dtype_bytes * sum(math.prod(s) for s, _ in shapes(cfg).values())
