"""Mixture-of-experts FFN with capacity-bounded scatter dispatch.

Design notes (TPU adaptation of the paper's FFN_PM tiling):

* Dispatch is *gather/scatter based*, not the one-hot-einsum dispatch of
  the Mixtral reference — the einsum form costs O(T^2 k/E) matmul FLOPs,
  which would swamp the expert compute in the roofline.  Scatter costs
  zero MXU FLOPs; only the router and the expert matmuls hit the MXU, so
  HLO FLOPs track 6·N_active·D.
* Capacity is per sequence (`C = ceil(S*k/E * capacity_factor)`), so the
  batch dimension stays cleanly sharded over the data axis and the expert
  dimension over the model axis (expert parallelism).
* Tokens over capacity are dropped (standard capacity-factor semantics);
  the residual connection keeps them intact.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoEConfig
from repro.distributed.sharding import constrain
from repro.models import layers
from repro.models.layers import build_dense, apply_dense, is_gated


def capacity(seq_len: int, m: MoEConfig) -> int:
    c = math.ceil(seq_len * m.experts_per_token / m.num_experts
                  * m.capacity_factor)
    return min(max(c, min(seq_len, 4)), seq_len)


def build_ffn(b, cfg: ArchConfig, d_ff: int, use_bias: bool = False) -> dict:
    """Dense (non-expert) FFN params — the paper's FFN1/FFN2(/FFN3)."""
    d = cfg.d_model
    p = {"w1": build_dense(b, d, d_ff, ("embed", "ffn"), use_bias=use_bias)}
    if is_gated(cfg.activation):
        p["wg"] = build_dense(b, d, d_ff, ("embed", "ffn"), use_bias=use_bias)
    p["w2"] = build_dense(b, d_ff, d, ("ffn", "embed"), use_bias=use_bias)
    return p


def apply_ffn(x: jax.Array, p: dict, activation: str) -> jax.Array:
    with jax.named_scope("ffn"):
        h = apply_dense(x, p["w1"])
        if is_gated(activation):
            h = layers.activate(apply_dense(x, p["wg"]), activation) * h
        else:
            h = layers.activate(h, activation)
        h = constrain(h, ("batch",) + (None,) * (h.ndim - 2) + ("ffn",))
        return apply_dense(h, p["w2"])


def build_moe(b, cfg: ArchConfig) -> dict:
    m = cfg.moe
    d = cfg.d_model
    p = {
        "router": b.param((d, m.num_experts), ("embed", "experts")),
        "w1": b.param((m.num_experts, d, m.expert_d_ff),
                      ("experts", "embed", "ffn")),
        "w2": b.param((m.num_experts, m.expert_d_ff, d),
                      ("experts", "ffn", "embed")),
    }
    if is_gated(cfg.activation):
        p["wg"] = b.param((m.num_experts, d, m.expert_d_ff),
                          ("experts", "embed", "ffn"))
    if m.num_shared_experts:
        p["shared"] = build_ffn(
            b, cfg, m.num_shared_experts * m.shared_expert_d_ff)
    return p


def route(x: jax.Array, router_w: jax.Array, m: MoEConfig
          ) -> tuple[jax.Array, jax.Array]:
    """Top-k routing.  Returns (weights [.., k], expert ids [.., k]).

    Softmax gating re-normalized over the selected k (Mixtral/granite
    style), scaled by ``router_scale`` (DeepSeek's routed_scaling_factor).
    """
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, m.experts_per_token)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return (m.router_scale * top_p), top_i


def _dispatch_one(x, top_w, top_i, p, m: MoEConfig, activation: str, cap: int):
    """Per-sequence expert dispatch.  x: [S, d]; top_*: [S, k]."""
    s, d = x.shape
    k = m.experts_per_token
    flat_e = top_i.reshape(s * k)                        # expert of each slot
    flat_w = top_w.reshape(s * k)
    # position of each slot within its expert (order-preserving)
    onehot = jax.nn.one_hot(flat_e, m.num_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot            # [S*k, E]
    flat_pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    dropped = flat_pos >= cap
    # scatter tokens into the [E, C, d] expert buffers ('drop' discards o.o.b.)
    src = jnp.repeat(x, k, axis=0)                       # [S*k, d] token copies
    e_idx = jnp.where(dropped, m.num_experts, flat_e)    # row E == trash
    buf = jnp.zeros((m.num_experts, cap, d), x.dtype)
    buf = buf.at[e_idx, jnp.minimum(flat_pos, cap - 1)].set(src, mode="drop")
    # expert FFNs, batched over E
    h = jnp.einsum("ecd,edf->ecf", buf, p["w1"].astype(x.dtype))
    if "wg" in p:
        g = jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(x.dtype))
        h = layers.activate(g, activation) * h
    else:
        h = layers.activate(h, activation)
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w2"].astype(x.dtype))
    # gather back and combine with routing weights
    got = out_buf[e_idx.clip(0, m.num_experts - 1), jnp.minimum(flat_pos, cap - 1)]
    got = jnp.where(dropped[:, None], 0.0, got) * flat_w[:, None].astype(x.dtype)
    return got.reshape(s, k, d).sum(axis=1)


def apply_moe(x: jax.Array, p: dict, cfg: ArchConfig) -> jax.Array:
    """x: [B, S, d] -> [B, S, d].  Routed experts + optional shared expert."""
    m = cfg.moe
    b_, s, d = x.shape
    cap = capacity(s, m)
    with jax.named_scope("ffn"):
        top_w, top_i = route(x, p["router"], m)
        routed = jax.vmap(
            lambda xi, wi, ii: _dispatch_one(xi, wi, ii, p, m,
                                             cfg.activation, cap)
        )(x, top_w, top_i)
        routed = constrain(routed, ("batch", None, None))
        if "shared" in p:
            routed = routed + apply_ffn(x, p["shared"], cfg.activation)
        return routed


def load_balance_loss(x: jax.Array, router_w: jax.Array, m: MoEConfig) -> jax.Array:
    """Auxiliary load-balancing loss (Switch/GShard form): E * sum_e f_e * p_e."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    _, top_i = jax.lax.top_k(probs, m.experts_per_token)
    chosen = jax.nn.one_hot(top_i, m.num_experts).sum(axis=-2)  # [..., E]
    f = jnp.mean(chosen.reshape(-1, m.num_experts), axis=0) / m.experts_per_token
    pbar = jnp.mean(probs.reshape(-1, m.num_experts), axis=0)
    return m.num_experts * jnp.sum(f * pbar)
