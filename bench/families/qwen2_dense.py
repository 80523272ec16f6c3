"""Qwen2-style dense decoder (Hugging Face ``Qwen2Model``), program side.

Qwen1.5 and CodeQwen1.5 share this block: RMSNorm -> attention with
biased q/k/v projections, rotary embeddings, grouped K/V heads ->
residual -> RMSNorm -> SwiGLU FFN -> residual; a final RMSNorm and the
vocabulary head (the embedding itself where tied).  Its plain reference
is ``bench/reference/qwen2_dense.py``.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from bench.families import kv_codec, runtime_spec
from bench.weights import DTYPES, draw


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim", d // h), "ff": cfg["intermediate_size"],
            "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"]}


def spec(cfg: dict, control: str | None):
    from repro.configs.base import ArchConfig

    m = dims(cfg)
    arch = ArchConfig(
        name=cfg["name"], family="dense", num_layers=m["layers"],
        d_model=m["d"], num_heads=m["h"], num_kv_heads=m["kv"],
        d_ff=m["ff"], vocab_size=m["vocab"], head_dim=m["hd"],
        activation="swiglu", norm="rmsnorm", qkv_bias=True,
        rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
        max_position_embeddings=cfg["max_position_embeddings"],
        source=cfg["source"])
    return runtime_spec(cfg, arch, control)


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


def make(cfg: dict, key, dtype) -> dict:
    return draw(shapes(cfg), key, dtype)


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


def bytes(cfg: dict) -> dict:
    """Weights, KV per token (K and V rows of every layer and KV head;
    an int8 row carries its f32 scale) and the whole KV pool."""
    m, sv = dims(cfg), cfg["serving"]
    size = lambda name: jnp.dtype(DTYPES[name]).itemsize  # noqa: E731
    row = m["hd"] + 4 if kv_codec(sv) == "int8" \
        else m["hd"] * size(sv["compute_dtype"])
    per_token = m["layers"] * 2 * m["kv"] * row
    return {"params": size(sv["param_dtype"]) * sum(
                math.prod(s) for s, _ in shapes(cfg).values()),
            "kv_per_token": per_token,
            "kv_pool": per_token * sv["max_batch"] * sv["max_len"]}


def matmul_per_token(cfg: dict) -> int:
    """Projections and FFN of one token through every layer."""
    m = dims(cfg)
    d, hd = m["d"], m["hd"]
    per_layer = (d * m["h"] * hd + 2 * d * m["kv"] * hd + m["h"] * hd * d
                 + 3 * d * m["ff"])
    return 2 * m["layers"] * per_layer


def attention_per_key(cfg: dict) -> int:
    """Scores and weighted values of one query against one key, all layers."""
    m = dims(cfg)
    return 4 * m["layers"] * m["h"] * m["hd"]


def head(cfg: dict) -> int:
    m = dims(cfg)
    return 2 * m["d"] * m["vocab"]
