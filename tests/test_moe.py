"""The expert layer and YaRN against the published equations.

* ``moe.route``'s sigmoid router picks inside the best expert groups by
  the biased scores and weighs by the unbiased ones (DeepSeek-V3's
  ``noaux_tc``);
* the dispatch is dropless: every token routed to one expert is
  computed, with no capacity;
* the held experts: a layer computes only the experts it holds, and
  counts their rows and the experts hit;
* YaRN's frequencies and MLA's softmax scale follow the published
  formula, and with no scaling the rotary path is the plain one, bit for
  bit.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, reduced
from repro.configs.base import MoEConfig, RopeScaling
from repro.models import attention, layers, moe
from repro.models.params import ParamBuilder

SIG = MoEConfig(num_experts=16, experts_per_token=4, expert_d_ff=8,
                scoring="sigmoid", n_group=4, topk_group=2,
                router_scale=2.5)


def _identity_router(m, bias):
    """Router weights that make the logits the input itself (d = E)."""
    return {"router": jnp.eye(m.num_experts, dtype=jnp.float32),
            "router_bias": jnp.asarray(bias, jnp.float32)}


def _literal_route(logits, bias, m):
    """DeepSeek-V3's MoEGate (noaux_tc), one token at a time in numpy."""
    out_w, out_i = [], []
    for row in np.asarray(logits, np.float64):
        scores = 1.0 / (1.0 + np.exp(-row))
        choice = scores + np.asarray(bias, np.float64)
        groups = choice.reshape(m.n_group, -1)
        gscore = np.sort(groups, axis=1)[:, -2:].sum(1)
        keep = np.argsort(-gscore, kind="stable")[:m.topk_group]
        mask = np.zeros(m.n_group, bool)
        mask[keep] = True
        tmp = np.where(np.repeat(mask, m.num_experts // m.n_group), choice,
                       -np.inf)
        idx = np.argsort(-tmp, kind="stable")[:m.experts_per_token]
        w = scores[idx] / scores[idx].sum() * m.router_scale
        out_w.append(w)
        out_i.append(idx)
    return np.array(out_w), np.array(out_i)


def test_sigmoid_router_matches_the_published_gate():
    key = jax.random.PRNGKey(3)
    logits = jax.random.normal(key, (64, 16))
    bias = 0.2 * jax.random.normal(jax.random.fold_in(key, 1), (16,))
    w, i = moe.route(logits, _identity_router(SIG, bias), SIG)
    lw, li = _literal_route(logits, bias, SIG)
    order = np.argsort(np.asarray(i), axis=1)
    lorder = np.argsort(li, axis=1)
    np.testing.assert_array_equal(np.take_along_axis(np.asarray(i), order, 1),
                                  np.take_along_axis(li, lorder, 1))
    np.testing.assert_allclose(np.take_along_axis(np.asarray(w), order, 1),
                               np.take_along_axis(lw, lorder, 1), rtol=1e-5)


def test_group_limit_and_bias_only_select():
    """Groups of 4: A holds the best expert alone, D the second; B and C
    have the best pairs.  Ungrouped, the top 4 would span A, D and B;
    limited to the best two groups it is B and C's.  The bias raises
    expert 0 into the pick but not into its weight."""
    x = np.full((1, 16), -4.0, np.float32)
    x[0, [0, 4, 5, 8, 9, 12]] = [2.2, 1.4, 1.1, 1.0, 1.0, 1.7]
    none = np.zeros(16, np.float32)
    _, i = moe.route(jnp.asarray(x), _identity_router(SIG, none), SIG)
    assert set(np.asarray(i)[0]) == {4, 5, 8, 9}
    ungrouped = dataclasses.replace(SIG, n_group=1, topk_group=1)
    _, i = moe.route(jnp.asarray(x), _identity_router(ungrouped, none),
                     ungrouped)
    assert set(np.asarray(i)[0]) == {0, 12, 4, 5}
    # a bias of +3 on expert 0's group mate 1 makes group A the best and
    # picks expert 1 although its score is the lowest
    bias = none.copy()
    bias[1] = 3.0
    w, i = moe.route(jnp.asarray(x), _identity_router(SIG, bias), SIG)
    picked = np.asarray(i)[0]
    assert 1 in picked and 0 in picked
    s = 1 / (1 + np.exp(-x[0, picked]))
    np.testing.assert_allclose(np.asarray(w)[0], s / s.sum() * 2.5,
                               rtol=1e-6)


def _moe_params(m, d, key, activation="swiglu"):
    cfg = dataclasses.replace(reduced(REGISTRY["granite-moe-1b-a400m"]),
                              d_model=d, moe=m, activation=activation)
    return cfg, moe.build_moe(ParamBuilder("init", key, jnp.float32), cfg)


def _expert(x, p, e):
    h = jax.nn.silu(x @ p["wg"][e]) * (x @ p["w1"][e])
    return h @ p["w2"][e]


def test_dispatch_is_dropless():
    """All 64 tokens pick expert 2 (k = 1): every one is computed, where
    a capacity of ceil(64 / 4 x 1.25) = 20 rows would have dropped 44."""
    m = MoEConfig(num_experts=4, experts_per_token=1, expert_d_ff=8)
    cfg, p = _moe_params(m, 16, jax.random.PRNGKey(0))
    p["router"] = jnp.zeros_like(p["router"]).at[:, 2].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (2, 32, 16)))
    out, counts = moe.apply_moe(x, p, cfg)
    want = _expert(x.reshape(64, 16), p, 2).reshape(2, 32, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    assert counts.tolist() == [64, 1]


def test_held_experts_compute_their_part_and_count_it():
    """A layer holding experts 4-7 of 16 gives, per token, the weighted
    sum over the picked experts it holds; dead tokens get nothing."""
    m = dataclasses.replace(SIG, expert_offset=4, held_experts=4)
    cfg, p = _moe_params(m, 16, jax.random.PRNGKey(2))
    p["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(4), (16,))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 16))
    live = jnp.arange(24)[None] < 20
    out, counts = moe.apply_moe(x, p, cfg, live)
    w, i = moe.route(x.reshape(24, 16), p, m)
    w, i = np.asarray(w), np.asarray(i)
    want = np.zeros((24, 16), np.float32)
    rows, hit = 0, set()
    for t in range(20):
        for j in range(4):
            e = int(i[t, j]) - 4
            if 0 <= e < 4:
                want[t] += w[t, j] * np.asarray(_expert(x[0, t], p, e))
                rows += 1
                hit.add(e)
    np.testing.assert_allclose(np.asarray(out)[0], want, rtol=1e-5,
                               atol=1e-6)
    assert rows and counts.tolist() == [rows, len(hit)]
    assert p["w1"].shape[0] == 4


def _yarn_literal(dim, base, rs):
    """DeepseekV3YarnRotaryEmbedding's inverse frequencies, as published."""
    def corr(rot):
        return (dim * math.log(rs.original_max_position_embeddings
                               / (rot * 2 * math.pi))) / (2 * math.log(base))
    low = max(math.floor(corr(rs.beta_fast)), 0)
    high = min(math.ceil(corr(rs.beta_slow)), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = 1.0 / (rs.factor * base ** (np.arange(0, dim, 2) / dim))
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def test_yarn_frequencies_and_scale_match_the_published_formula():
    cfg = REGISTRY["deepseek-v3-671b"]
    rs = cfg.rope_scaling
    got = layers.rope_frequencies(64, cfg.rope_theta, rs)
    np.testing.assert_allclose(np.asarray(got),
                               _yarn_literal(64, cfg.rope_theta, rs),
                               rtol=1e-6)
    # the published config: factor 40, mscale = mscale_all_dim = 1
    m = 0.1 * math.log(40) + 1
    assert attention.mla_score_divisor(cfg) == pytest.approx(
        math.sqrt(192) / m ** 2, rel=1e-12)
    assert layers.rope_mscale(rs) == 1.0
    # positions past the original context rotate slower in the low pairs
    assert float(got[-1]) == pytest.approx(
        float(layers.rope_frequencies(64, cfg.rope_theta)[-1]) / 40)


def _rope_before_scaling(x, positions, theta):
    """apply_rope as it was before rope_scaling existed."""
    d = x.shape[-1]
    half = d // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32)
                                / half))
    angles = positions[..., :, None].astype(jnp.float32) * inv_freq
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_no_scaling_is_the_plain_rotary_bit_for_bit(dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 4, 64)).astype(dtype)
    pos = jnp.arange(40)[None] + jnp.array([[0], [1000]])
    got = jax.jit(lambda x, p: layers.apply_rope(x, p, 1e6))(x, pos)
    want = jax.jit(lambda x, p: _rope_before_scaling(x, p, 1e6))(x, pos)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    plain = dataclasses.replace(REGISTRY["deepseek-v3-671b"],
                                rope_scaling=None)
    assert attention.mla_score_divisor(plain) == math.sqrt(192)


def test_reduced_deepseek_keeps_the_router_and_yarn():
    cfg = reduced(REGISTRY["deepseek-v3-671b"])
    assert cfg.moe.scoring == "sigmoid" and cfg.moe.n_group == 1
    assert cfg.moe.router_scale == 2.5
    assert cfg.rope_scaling == RopeScaling(
        factor=40.0, original_max_position_embeddings=4096, beta_fast=32.0,
        beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)
