"""Plain forward pass of DeepSeek-V3, in float32.

The block as published (Hugging Face ``modeling_deepseek.py``,
arXiv:2412.19437): RMSNorm -> multi-head latent attention -> residual
-> RMSNorm -> FFN -> residual; a final RMSNorm and the vocabulary head.

* Attention is the naive form: the query from ``q_b(norm(q_a(x)))``,
  the key and value of every head expanded from the normed latent by
  ``kv_b``, one 64-wide rotary key shared by the heads.  Rotary
  embeddings rotate interleaved pairs with YaRN's frequencies and
  cos/sin factor; the softmax scale is qk_head_dim^-1/2 times
  mscale(factor, mscale_all_dim)^2.
* The first ``first_k_dense_replace`` layers have a dense SwiGLU FFN.
  The others route: sigmoid scores; selection scores add the correction
  bias; a group scores the sum of its two best; the ``topk_group`` best
  groups are kept (the rest -inf) and the top ``num_experts_per_tok``
  taken; the weights are the picked scores without the bias, normalized
  and scaled by ``routed_scaling_factor``.  Of the routed experts only
  those the configuration holds (``n_routed_experts`` from
  ``expert_offset``) are computed, densely over every token and weighted
  by the token's gate for them (0 where not picked); the shared expert
  is added to every token.

No cache, no paging, no batching: one packed row of whole sequences,
each token attending to the earlier tokens of its own sequence, in
query and head blocks so that it fits one chip.  Weights are the
benchmark's (``bench.families.deepseek_v3.make``) in their published
layout, read in float32 with matmuls at the highest precision.  Imports
nothing of the serving program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PREC = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
HEAD_BLOCK = 16
LOGIT_BLOCK = 512


def _f32(a):
    return a.astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(a, _f32(b), precision=PREC)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(gain)


def _yarn_mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def _yarn(cfg):
    """(inverse frequencies [rope/2], cos/sin factor, softmax scale)."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    rs = cfg["rope_scaling"]
    factor = rs["factor"]
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inter = extra / factor

    def corr(rot):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv = inter * (1 - mask) + extra * mask
    cs = _yarn_mscale(factor, rs["mscale"]) \
        / _yarn_mscale(factor, rs["mscale_all_dim"])
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5
    if rs["mscale_all_dim"]:
        scale *= _yarn_mscale(factor, rs["mscale_all_dim"]) ** 2
    return inv, cs, scale


def _rope(x, pos, inv, cs):
    """x [S, heads, dim] in interleaved pairs -> rotated, halves order."""
    s_len, heads, dim = x.shape
    x = x.reshape(s_len, heads, dim // 2, 2).swapaxes(-1, -2) \
        .reshape(s_len, heads, dim)
    ang = pos[:, None].astype(jnp.float32) * inv
    emb = jnp.concatenate([ang, ang], -1)[:, None, :]
    cos, sin = jnp.cos(emb) * cs, jnp.sin(emb) * cs
    rot = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + rot * sin


def _attention(q, k, v, seg, scale):
    """q/k [S, h, qk], v [S, h, vd]; causal within a segment."""
    s_len, h, _ = q.shape
    vd = v.shape[-1]
    qb_len, hb_len = min(QUERY_BLOCK, s_len), min(HEAD_BLOCK, h)
    idx = jnp.arange(s_len)

    def block(ij):
        i, j = ij // (h // hb_len), ij % (h // hb_len)
        qb = jax.lax.dynamic_slice(q, (i * qb_len, j * hb_len, 0),
                                   (qb_len, hb_len, q.shape[-1]))
        kb = jax.lax.dynamic_slice_in_dim(k, j * hb_len, hb_len, 1)
        vb = jax.lax.dynamic_slice_in_dim(v, j * hb_len, hb_len, 1)
        qi = jax.lax.dynamic_slice_in_dim(idx, i * qb_len, qb_len)
        qs = jax.lax.dynamic_slice_in_dim(seg, i * qb_len, qb_len)
        s = jnp.einsum("qhd,khd->hqk", qb, kb, precision=PREC) * scale
        ok = (qs[:, None] == seg[None, :]) & (idx[None, :] <= qi[:, None])
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vb, precision=PREC)

    nq, nh = s_len // qb_len, h // hb_len
    out = jax.lax.map(block, jnp.arange(nq * nh))      # [nq*nh, Qb, Hb, vd]
    out = out.reshape(nq, nh, qb_len, hb_len, vd).transpose(0, 2, 1, 3, 4)
    return out.reshape(s_len, h * vd)


def _mla(cfg, lw, x, pos, seg, yarn):
    s_len = x.shape[0]
    h, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]
    inv, cs, scale = yarn
    q = _mm(_rms(_mm(x, lw["q_a"]), lw["q_a_norm"], eps), lw["q_b"])
    q = q.reshape(s_len, h, nope + rope)
    kv = _mm(x, lw["kv_a"])
    c = _rms(kv[:, :rank], lw["kv_a_norm"], eps)
    k_pe = _rope(kv[:, None, rank:], pos, inv, cs)           # [S, 1, rope]
    q_pe = _rope(q[..., nope:], pos, inv, cs)
    kvb = _mm(c, lw["kv_b"]).reshape(s_len, h, nope + vd)
    qf = jnp.concatenate([q[..., :nope], q_pe], -1)
    kf = jnp.concatenate([kvb[..., :nope],
                          jnp.broadcast_to(k_pe, (s_len, h, rope))], -1)
    o = _attention(qf, kf, kvb[..., nope:], seg, scale)
    return _mm(o, lw["o"])


def _swiglu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def route(cfg, x, router, bias):
    """(gate weights [S, k], expert ids [S, k]) over all routed experts."""
    scores = jax.nn.sigmoid(_mm(x, router))                  # [S, E]
    choice = scores + _f32(bias)
    s_len, n_e = scores.shape
    grouped = choice.reshape(s_len, cfg["n_group"], -1)
    group_scores = jax.lax.top_k(grouped, 2)[0].sum(-1)      # [S, G]
    _, group_idx = jax.lax.top_k(group_scores, cfg["topk_group"])
    group_mask = jnp.zeros_like(group_scores).at[
        jnp.arange(s_len)[:, None], group_idx].set(1.0)
    score_mask = jnp.repeat(group_mask, n_e // cfg["n_group"], axis=-1)
    tmp = jnp.where(score_mask > 0, choice, -jnp.inf)
    _, topk_idx = jax.lax.top_k(tmp, cfg["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, topk_idx, axis=-1)
    weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    return weight * cfg["routed_scaling_factor"], topk_idx


def _moe(cfg, lw, x):
    weight, idx = route(cfg, x, lw["router"], lw["router_bias"])
    held = cfg["n_routed_experts"]
    local = idx - cfg["expert_offset"]
    # gate of each held expert for each token: 0 where it was not picked
    gate = jnp.einsum("sk,ske->se", weight,
                      jax.nn.one_hot(local, held, dtype=jnp.float32))
    hid = jax.nn.silu(jnp.einsum("sd,edf->sef", x, _f32(lw["expert_gate"]),
                                 precision=PREC)) \
        * jnp.einsum("sd,edf->sef", x, _f32(lw["expert_up"]),
                     precision=PREC)
    routed = jnp.einsum("sef,efd->sd", hid * gate[..., None],
                        _f32(lw["expert_down"]), precision=PREC)
    return routed + _swiglu(x, lw["shared_gate"], lw["shared_up"],
                            lw["shared_down"])


def layer_weights(cfg: dict, w: dict, i: int) -> dict:
    """Layer ``i``'s weights by their short names: ``dense<i>.*`` for the
    dense layers, entry ``i - first_k_dense_replace`` of ``moe.*``."""
    dense = cfg["first_k_dense_replace"]
    if i < dense:
        pre = f"dense{i}."
        return {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}
    return {k[4:]: v[i - dense] for k, v in w.items()
            if k.startswith("moe.")}


def hidden(cfg: dict, w: dict, tokens, pos, seg):
    """Final-normed hidden states [S, d] of one packed row."""
    eps, dense = cfg["rms_norm_eps"], cfg["first_k_dense_replace"]
    yarn = _yarn(cfg)
    x = _f32(w["embed"])[tokens]
    for i in range(cfg["num_hidden_layers"]):
        lw = layer_weights(cfg, w, i)
        x = x + _mla(cfg, lw, _rms(x, lw["ln1"], eps), pos, seg, yarn)
        a = _rms(x, lw["ln2"], eps)
        if i < dense:
            x = x + _swiglu(a, lw["gate"], lw["up"], lw["down"])
        else:
            x = x + _moe(cfg, lw, a)
    return _rms(x, w["final_norm"], eps)


def gaps(cfg: dict, w: dict, tokens, pos, seg, targets):
    """For every row position: the best logit less the logit of
    ``targets`` there, and the best token.  ``tokens``/``pos``/``seg``/
    ``targets`` are [S] int32, S a multiple of 512 or below it."""
    x = hidden(cfg, w, tokens, pos, seg)
    table = w["lm_head"]
    size = min(LOGIT_BLOCK, tokens.shape[0])

    def block(b):
        xb = jax.lax.dynamic_slice_in_dim(x, b * size, size)
        tb = jax.lax.dynamic_slice_in_dim(targets, b * size, size)
        lg = jnp.einsum("sd,vd->sv", xb, _f32(table), precision=PREC)
        best = lg.max(-1)
        got = jnp.take_along_axis(lg, tb[:, None], axis=-1)[:, 0]
        return best - got, lg.argmax(-1).astype(jnp.int32)

    gap, top = jax.lax.map(block, jnp.arange(tokens.shape[0] // size))
    return gap.reshape(-1), top.reshape(-1)


def logits(cfg: dict, w: dict, tokens, pos, seg):
    """Logits [S, vocab] of one packed row (for small rows)."""
    x = hidden(cfg, w, tokens, pos, seg)
    return jnp.einsum("sd,vd->sv", x, _f32(w["lm_head"]), precision=PREC)
