"""DeepSeek-V3 (Hugging Face ``DeepseekV3Model``), program side.

Every layer: RMSNorm -> multi-head latent attention (a LoRA-compressed
query, a shared 512-wide key/value latent and a 64-wide rotary key,
YaRN-scaled) -> residual -> RMSNorm -> FFN -> residual.  The first
``first_k_dense_replace`` layers have a dense SwiGLU FFN; the rest a
mixture of experts: a sigmoid router with a selection-only correction
bias, limited to the ``topk_group`` best of ``n_group`` groups, top
``num_experts_per_tok``, weights normalized and scaled by
``routed_scaling_factor``, plus one shared expert.  A final RMSNorm and
an untied vocabulary head.  Its plain reference is
``bench/reference/deepseek_v3.py``.

A configuration holds a chip's share of an expert-parallel deployment:
``n_routed_experts`` experts, from ``expert_offset``, of the
``published["n_routed_experts"]`` the router scores.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from bench.families import kv_codec, runtime_spec
from bench.weights import DTYPES, draw


def dims(cfg: dict) -> dict:
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "dense_ff": cfg["intermediate_size"],
            "ff": cfg["moe_intermediate_size"], "layers": n, "dense": dense,
            "moe": n - dense, "held": cfg["n_routed_experts"],
            "routed": cfg["published"]["n_routed_experts"],
            "offset": cfg["expert_offset"], "k": cfg["num_experts_per_tok"],
            "shared": cfg["n_shared_experts"], "vocab": cfg["vocab_size"]}


# what the program serves of the published block; a configuration that
# says otherwise is refused, not served as something else
SERVED = {"hidden_act": "silu", "scoring_func": "sigmoid",
          "topk_method": "noaux_tc", "norm_topk_prob": True,
          "tie_word_embeddings": False, "attention_bias": False,
          "rms_norm_eps": 1e-6}


def spec(cfg: dict, control: str | None):
    from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, \
        RopeScaling

    for key, want in SERVED.items():
        if cfg[key] != want:
            raise ValueError(f"{cfg['name']}: {key} {cfg[key]!r}; the "
                             f"program serves {want!r} only")
    rs = cfg["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError(f"rope_scaling type {rs['type']!r}: yarn only")
    m = dims(cfg)
    arch = ArchConfig(
        name=cfg["name"], family="moe", num_layers=m["layers"],
        d_model=m["d"], num_heads=m["h"], num_kv_heads=m["h"],
        d_ff=m["ff"], vocab_size=m["vocab"], head_dim=m["v"],
        activation="swiglu", norm="rmsnorm", rope_theta=cfg["rope_theta"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rope_scaling=RopeScaling(
            factor=rs["factor"],
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
            mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"]),
        source=cfg["source"],
        moe=MoEConfig(
            num_experts=m["routed"], experts_per_token=m["k"],
            expert_d_ff=m["ff"], num_shared_experts=m["shared"],
            shared_expert_d_ff=m["ff"], first_k_dense=m["dense"],
            dense_d_ff=m["dense_ff"],
            router_scale=cfg["routed_scaling_factor"], scoring="sigmoid",
            n_group=cfg["n_group"], topk_group=cfg["topk_group"],
            expert_offset=m["offset"], held_experts=m["held"]),
        mla=MLAConfig(q_lora_rank=m["q_rank"], kv_lora_rank=m["kv_rank"],
                      qk_nope_head_dim=m["nope"],
                      qk_rope_head_dim=m["rope"], v_head_dim=m["v"]))
    return runtime_spec(cfg, arch, control)


def _attention(cfg: dict, stack: tuple) -> dict:
    """One attention block's weights and norms, ``stack`` leading dims."""
    m = dims(cfg)
    d, h = m["d"], m["h"]
    return {"ln1": (stack + (d,), "gain"), "ln2": (stack + (d,), "gain"),
            "q_a": (stack + (d, m["q_rank"]), "proj"),
            "q_a_norm": (stack + (m["q_rank"],), "gain"),
            "q_b": (stack + (m["q_rank"], h * (m["nope"] + m["rope"])),
                    "proj"),
            "kv_a": (stack + (d, m["kv_rank"] + m["rope"]), "proj"),
            "kv_a_norm": (stack + (m["kv_rank"],), "gain"),
            "kv_b": (stack + (m["kv_rank"], h * (m["nope"] + m["v"])),
                     "proj"),
            "o": (stack + (h * m["v"], d), "proj")}


def shapes(cfg: dict) -> dict:
    """name -> (shape, kind) of every weight, in the published layout
    (``q_b`` and ``kv_b`` per head, rope dims in interleaved pairs).  The
    dense layers' weights are named ``dense<i>.<name>``, one layer each,
    and the MoE layers' ``moe.<name>``, stacked: the program's own
    grouping, so that no layer is sliced out of a stack and copied."""
    m = dims(cfg)
    d, e, ff = m["d"], m["held"], m["ff"]
    shared = m["shared"] * ff
    out = {"embed": ((m["vocab"], d), "embed"),
           "lm_head": ((m["vocab"], d), "embed"),
           "final_norm": ((d,), "gain")}
    for i in range(m["dense"]):
        layer = dict(_attention(cfg, ()),
                     gate=((d, m["dense_ff"]), "proj"),
                     up=((d, m["dense_ff"]), "proj"),
                     down=((m["dense_ff"], d), "proj"))
        out.update({f"dense{i}.{k}": v for k, v in layer.items()})
    nm = (m["moe"],)
    layer = dict(_attention(cfg, nm),
                 router=(nm + (d, m["routed"]), "proj"),
                 router_bias=(nm + (m["routed"],), "bias"),
                 expert_gate=(nm + (e, d, ff), "proj"),
                 expert_up=(nm + (e, d, ff), "proj"),
                 expert_down=(nm + (e, ff, d), "proj"),
                 shared_gate=(nm + (d, shared), "proj"),
                 shared_up=(nm + (d, shared), "proj"),
                 shared_down=(nm + (shared, d), "proj"))
    out.update({f"moe.{k}": v for k, v in layer.items()})
    return out


def make(cfg: dict, key, dtype) -> dict:
    return draw(shapes(cfg), key, dtype)


def rope_halves(x, rope: int):
    """The last ``rope`` columns of ``x`` from interleaved pairs (x0, x1),
    (x2, x3), ... to the program's (first half, second half) order."""
    r = x[..., -rope:]
    return jnp.concatenate([x[..., :-rope], r[..., 0::2], r[..., 1::2]],
                           axis=-1)


def to_program(cfg: dict, w: dict) -> dict:
    """The serving program's parameter tree over the same weights: the
    rope columns of ``q_b`` and ``kv_a`` permuted to the program's
    rotation, ``kv_b`` split into its key and value halves (the only
    arrays made anew)."""
    m = dims(cfg)
    h, qk = m["h"], m["nope"] + m["rope"]

    def block(pre):
        lead = w[pre + "q_b"].shape[:-2]
        q_b = rope_halves(w[pre + "q_b"].reshape(
            *lead, m["q_rank"], h, qk), m["rope"]).reshape(
            *lead, m["q_rank"], h * qk)
        kv_b = w[pre + "kv_b"].reshape(*lead, m["kv_rank"], h,
                                       m["nope"] + m["v"])
        return {"ln1": {"scale": w[pre + "ln1"]},
                "ln2": {"scale": w[pre + "ln2"]},
                "attn": {
                    "q_down": {"kernel": w[pre + "q_a"]},
                    "q_norm": {"scale": w[pre + "q_a_norm"]},
                    "q_up": {"kernel": q_b},
                    "kv_down": {"kernel": rope_halves(w[pre + "kv_a"],
                                                      m["rope"])},
                    "kv_norm": {"scale": w[pre + "kv_a_norm"]},
                    "k_up": {"kernel": kv_b[..., :m["nope"]].reshape(
                        *lead, m["kv_rank"], h * m["nope"])},
                    "v_up": {"kernel": kv_b[..., m["nope"]:].reshape(
                        *lead, m["kv_rank"], h * m["v"])},
                    "wo": {"kernel": w[pre + "o"]}}}

    dense = [dict(block(f"dense{i}."),
                  ffn={"w1": {"kernel": w[f"dense{i}.up"]},
                       "wg": {"kernel": w[f"dense{i}.gate"]},
                       "w2": {"kernel": w[f"dense{i}.down"]}})
             for i in range(m["dense"])]
    tree = {"embed": {"table": w["embed"]},
            "lm_head": {"table": w["lm_head"]},
            "final_norm": {"scale": w["final_norm"]},
            "layers": dict(block("moe."), moe={
                "router": w["moe.router"],
                "router_bias": w["moe.router_bias"],
                "w1": w["moe.expert_up"], "wg": w["moe.expert_gate"],
                "w2": w["moe.expert_down"],
                "shared": {"w1": {"kernel": w["moe.shared_up"]},
                           "wg": {"kernel": w["moe.shared_gate"]},
                           "w2": {"kernel": w["moe.shared_down"]}}})}
    if dense:
        tree["dense_prefix"] = dense
    return tree


def bytes(cfg: dict) -> dict:
    """Weights, the latent cache per token (the 512-wide latent and the
    64-wide rope key of every layer; an int8 row carries its f32 scale)
    and the whole pool."""
    m, sv = dims(cfg), cfg["serving"]
    size = lambda name: jnp.dtype(DTYPES[name]).itemsize  # noqa: E731
    if kv_codec(sv) == "int8":
        row = m["kv_rank"] + m["rope"] + 2 * 4
    else:
        row = (m["kv_rank"] + m["rope"]) * size(sv["compute_dtype"])
    per_token = m["layers"] * row
    return {"params": size(sv["param_dtype"]) * sum(
                math.prod(s) for s, _ in shapes(cfg).values()),
            "kv_per_token": per_token,
            "kv_pool": per_token * sv["max_batch"] * sv["max_len"]}


def matmul_per_token(cfg: dict) -> int:
    """One token through every layer as the program computes it: the
    latent attention's projections with k_up and v_up absorbed per head,
    the dense FFNs, and per MoE layer the router, the shared expert and
    the expected share of the routed experts held here (``k x held /
    routed`` expert rows a token)."""
    m = dims(cfg)
    d, h = m["d"], m["h"]
    attn = (d * m["q_rank"] + m["q_rank"] * h * (m["nope"] + m["rope"])
            + d * (m["kv_rank"] + m["rope"]) + h * m["nope"] * m["kv_rank"]
            + h * m["kv_rank"] * m["v"] + h * m["v"] * d)
    expert = 3 * d * m["ff"]
    moe = (d * m["routed"] + m["shared"] * expert
           + expert * m["k"] * m["held"] // m["routed"])
    return 2 * (m["layers"] * attn + m["dense"] * 3 * d * m["dense_ff"]
                + m["moe"] * moe)


def attention_per_key(cfg: dict) -> int:
    """Scores over the latent and the rope key, and values over the
    latent, of one query against one key, every head of every layer."""
    m = dims(cfg)
    return 2 * m["layers"] * m["h"] * (2 * m["kv_rank"] + m["rope"])


def head(cfg: dict) -> int:
    m = dims(cfg)
    return 2 * m["d"] * m["vocab"]


def expert_flops(cfg: dict, rows: float) -> float:
    """The held experts' grouped matmuls over ``rows`` (token, expert)
    rows: gate, up and down, 2 d ff each."""
    m = dims(cfg)
    return 2 * 3 * m["d"] * m["ff"] * rows


def expert_bytes(cfg: dict, rows: float, hit: float) -> float:
    """The least HBM traffic of those matmuls: each of ``hit`` experts'
    three weight matrices read once, each row read in and written out."""
    m = dims(cfg)
    size = jnp.dtype(DTYPES[cfg["serving"]["param_dtype"]]).itemsize
    act = jnp.dtype(DTYPES[cfg["serving"]["compute_dtype"]]).itemsize
    return hit * 3 * m["d"] * m["ff"] * size + rows * 2 * m["d"] * act
