"""Mixture-of-experts FFN: a router over every expert, a dropless
token-batched dispatch to the experts this chip holds.

* **Router** (``route``): softmax top-k (Mixtral / granite), or
  DeepSeek-V3's sigmoid ``noaux_tc`` router, whose correction bias picks
  the experts but never weighs them, inside the ``topk_group`` best of
  ``n_group`` expert groups.  The k gate weights are normalized over the
  k and scaled by ``router_scale``.  Scores are float32.
* **Held experts**: ``MoEConfig.expert_offset`` / ``held_experts`` name
  the experts whose weights live here (expert parallelism: each chip
  holds a slice of every MoE layer).  The router keeps its whole width;
  the layer computes the part of the result its own experts give, and a
  token routed only elsewhere gets nothing from them.  Parameters hold
  the held experts alone.
* **Dispatch** is token-batched and dropless: the step's tokens are
  flattened, every live (token, expert) pair that lands on a held expert
  is sorted by expert, one grouped matmul per weight matrix
  (``kernels.grouped_matmul``, a Pallas kernel that reads only the
  experts hit) runs the held experts over their rows, and the weighted
  rows are added back to their tokens.  No capacity: a token's output depends on that token
  alone, so a served stream matches a reference whatever the batch.
  Dead lanes (``live`` False: padded mixed-step lanes, idle slots) go to
  no expert.
* The shared experts, which every chip computes alike, are added once.

``apply_moe`` also returns what the held experts computed, int32
``[rows, experts hit]``: (token, expert) pairs computed, and held
experts with at least one row.  Named scopes ``moe.route``,
``moe.dispatch``, ``moe.experts``, ``moe.combine`` and ``moe.shared``
sit inside ``ffn`` (``serving.events.SCOPE_NAMES``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoEConfig
from repro.distributed.sharding import constrain
from repro.kernels.grouped_matmul import grouped_matmul
from repro.models import layers
from repro.models.layers import build_dense, apply_dense, is_gated


def build_ffn(b, cfg: ArchConfig, d_ff: int, use_bias: bool = False) -> dict:
    """Dense (non-expert) FFN params — the paper's FFN1/FFN2(/FFN3)."""
    d = cfg.d_model
    p = {"w1": build_dense(b, d, d_ff, ("embed", "ffn"), use_bias=use_bias)}
    if is_gated(cfg.activation):
        p["wg"] = build_dense(b, d, d_ff, ("embed", "ffn"), use_bias=use_bias)
    p["w2"] = build_dense(b, d_ff, d, ("ffn", "embed"), use_bias=use_bias)
    return p


def _ffn(x: jax.Array, p: dict, activation: str) -> jax.Array:
    h = apply_dense(x, p["w1"])
    if is_gated(activation):
        h = layers.activate(apply_dense(x, p["wg"]), activation) * h
    else:
        h = layers.activate(h, activation)
    h = constrain(h, ("batch",) + (None,) * (h.ndim - 2) + ("ffn",))
    return apply_dense(h, p["w2"])


def apply_ffn(x: jax.Array, p: dict, activation: str) -> jax.Array:
    with jax.named_scope("ffn"):
        return _ffn(x, p, activation)


def build_moe(b, cfg: ArchConfig) -> dict:
    m = cfg.moe
    d, e = cfg.d_model, m.n_held
    p = {
        "router": b.param((d, m.num_experts), ("embed", "experts")),
        "w1": b.param((e, d, m.expert_d_ff), ("experts", "embed", "ffn")),
        "w2": b.param((e, m.expert_d_ff, d), ("experts", "ffn", "embed")),
    }
    if m.scoring == "sigmoid":
        p["router_bias"] = b.param((m.num_experts,), ("experts",),
                                   init="zeros")
    if is_gated(cfg.activation):
        p["wg"] = b.param((e, d, m.expert_d_ff), ("experts", "embed", "ffn"))
    if m.num_shared_experts:
        p["shared"] = build_ffn(
            b, cfg, m.num_shared_experts * m.shared_expert_d_ff)
    return p


def route(x: jax.Array, p: dict, m: MoEConfig
          ) -> tuple[jax.Array, jax.Array]:
    """Top-k routing over every expert.  Returns (gate weights [.., k]
    float32, expert ids [.., k]).

    ``softmax``: top k of the softmax.  ``sigmoid`` (DeepSeek-V3
    ``noaux_tc``): scores s = sigmoid(logits); the selection scores
    s + ``router_bias`` rank the groups (each the sum of its two best)
    and, inside the ``topk_group`` best groups, the experts; the weights
    are the picked s without the bias.  Either way the k weights are
    normalized over the k and scaled by ``router_scale``."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if m.scoring == "softmax":
        top_s, top_i = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                     m.experts_per_token)
    else:
        scores = jax.nn.sigmoid(logits)
        choice = scores + p["router_bias"].astype(jnp.float32)
        if m.n_group > 1:
            grouped = choice.reshape(*choice.shape[:-1], m.n_group, -1)
            group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)
            _, keep = jax.lax.top_k(group_score, m.topk_group)
            kept = jnp.any(keep[..., :, None]
                           == jnp.arange(m.n_group), axis=-2)   # [.., G]
            choice = jnp.where(kept[..., None], grouped,
                               -jnp.inf).reshape(choice.shape)
        _, top_i = jax.lax.top_k(choice, m.experts_per_token)
        top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    top_s = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    return m.router_scale * top_s, top_i


# the held experts' weights, [e, ...] a layer
EXPERT_WEIGHTS = ("w1", "wg", "w2")


def _held_experts(x: jax.Array, top_w: jax.Array, top_i: jax.Array,
                  live: jax.Array, p: dict, m: MoEConfig, activation: str,
                  layer=None):
    """The held experts' part of the routed output for tokens ``x``
    [T, d] (``top_*`` [T, k], ``live`` [T]): [T, d] float32, and int32
    [rows, experts hit].  ``layer``: see ``apply_moe``."""
    t, d = x.shape
    k, e = m.experts_per_token, m.n_held
    with jax.named_scope("moe.dispatch"):
        local = top_i.reshape(t * k) - m.expert_offset
        held = (local >= 0) & (local < e) & jnp.repeat(live, k)
        key = jnp.where(held, local, e)              # e: not computed here
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((e + 1,), jnp.int32).at[key].add(1)[:e]
        token = order // k
        rows = x[token]                              # [T*k, d], by expert
        valid = held[order]
    with jax.named_scope("moe.experts"):
        h = grouped_matmul(rows, p["w1"], sizes, layer)
        if "wg" in p:
            h = layers.activate(grouped_matmul(rows, p["wg"], sizes, layer),
                                activation) * h
        else:
            h = layers.activate(h, activation)
        y = grouped_matmul(h, p["w2"], sizes, layer)
    with jax.named_scope("moe.combine"):
        # rows past the groups hold no held pair and are left unwritten:
        # they are cut, not weighted
        w = top_w.reshape(t * k)[order][:, None]
        y = jnp.where(valid[:, None], y.astype(jnp.float32) * w, 0.0)
        out = jnp.zeros((t, d), jnp.float32).at[token].add(y)
    counts = jnp.stack([sizes.sum(), (sizes > 0).sum()]).astype(jnp.int32)
    return out, counts


def apply_moe(x: jax.Array, p: dict, cfg: ArchConfig,
              live: jax.Array | None = None, layer=None
              ) -> tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> ([B, S, d], int32 [rows, experts hit]).  The held
    routed experts plus the shared expert; ``live`` ([B, S] bool, default
    all) marks the tokens that are routed at all.  With ``layer``, the
    ``EXPERT_WEIGHTS`` of ``p`` are stacked over the MoE layers and this
    layer's are ``[layer]`` (the serving layer scan passes them whole,
    ``kernels.grouped_matmul``)."""
    m = cfg.moe
    b_, s, d = x.shape
    flat = x.reshape(b_ * s, d)
    live = jnp.ones((b_ * s,), bool) if live is None \
        else live.reshape(b_ * s)
    with jax.named_scope("ffn"):
        with jax.named_scope("moe.route"):
            top_w, top_i = route(flat, p, m)
        routed, counts = _held_experts(flat, top_w, top_i, live, p, m,
                                       cfg.activation, layer)
        out = constrain(routed.astype(x.dtype).reshape(b_, s, d),
                        ("batch", None, None))
        if "shared" in p:
            with jax.named_scope("moe.shared"):
                out = out + _ffn(x, p["shared"], cfg.activation)
        return out, counts


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
