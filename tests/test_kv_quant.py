"""The int8 KV-cache codec: round-trips, decode equivalence, and
stream identity across every serving mode.

The tentpole claims under test:

* the codec round-trips within the symmetric-int8 error bound
  (half a quantization step per element, per-row scales),
* prefill + decode with an int8 cache tracks the float-cache logits
  within a small tolerance for GQA *and* MLA,
* greedy streams are token-identical to the float cache on the
  test-size models across dense / paged / chunked / bucketed serving,
  the Pallas kernels serve the gather path's streams from the same int8
  pool, and a quantized fleet (int8 weight table + int8 cache) serves a
  mixed workload from ONE compiled step.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_cfg
from repro.core.kv_quant import CacheCodec, cache_put
from repro.core.spec import (ExecutionSpec, MemorySpec, RuntimeSpec,
                             SchedulerSpec, maxima_for)
from repro.models.model import Model, ModelOptions
from repro.serving.engine import ServingEngine
from repro.serving.sampling import SamplingParams

INT8 = CacheCodec("int8")
FLOAT = CacheCodec("compute")


# ---------------------------------------------------------------------------
# Codec round-trip properties
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape,seed", [((4, 7, 16), 0), ((2, 3, 5, 64), 1),
                                        ((1, 128), 2), ((6, 1), 3)])
def test_roundtrip_error_bound(shape, seed):
    """|x - decode(encode(x))| <= scale/2 + eps, scale = amax(row)/127."""
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32) * 3.0
    q, scale = INT8.encode(x)
    back = INT8.decode(q, scale, jnp.float32)
    bound = np.asarray(scale)[..., None] * 0.5 + 1e-6
    assert np.all(np.abs(np.asarray(back - x)) < bound)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert scale.shape == shape[:-1]


def test_roundtrip_extremes_and_zeros():
    # a zero row must round-trip to exactly zero (eps floor, no NaN)
    z = jnp.zeros((3, 8))
    q, s = INT8.encode(z)
    assert float(jnp.abs(INT8.decode(q, s)).max()) == 0.0
    # amax element is exactly representable (127 * amax/127)
    x = jnp.asarray([[5.0, -2.5, 0.125, 0.0]])
    q, s = INT8.encode(x)
    assert int(jnp.abs(q).max()) == 127
    assert abs(float(INT8.decode(q, s, jnp.float32)[0, 0]) - 5.0) < 1e-6


def test_roundtrip_scale_invariance():
    """Per-row scaling means scaling one row never perturbs another."""
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 16), jnp.float32)
    q1, s1 = INT8.encode(x)
    big = x.at[0].mul(1000.0)
    q2, s2 = INT8.encode(big)
    np.testing.assert_array_equal(np.asarray(q1[1:]), np.asarray(q2[1:]))
    np.testing.assert_allclose(np.asarray(s1[1:]), np.asarray(s2[1:]))


def test_compute_codec_is_identity():
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 8), jnp.float32)
    vals, scale = FLOAT.store(x, jnp.bfloat16)
    assert scale is None and vals.dtype == jnp.bfloat16
    assert FLOAT.load(vals, None) is vals
    v, s = FLOAT.cache_arrays((2, 4, 8))
    assert s is None and v.dtype == jnp.bfloat16


def test_cache_put_writes_values_and_scales():
    vals = jnp.zeros((4, 8, 2, 16), jnp.int8)
    scales = jnp.zeros((4, 8, 2), jnp.float32)
    new = jax.random.normal(jax.random.PRNGKey(6), (4, 2, 16), jnp.float32)
    q, s = INT8.encode(new)
    rows = jnp.arange(4)
    idx = jnp.asarray([0, 3, 7, 2])
    v2, s2 = cache_put(vals, scales, (rows, idx), q, s)
    back = INT8.decode(v2[rows, idx], s2[rows, idx], jnp.float32)
    np.testing.assert_allclose(np.asarray(back), np.asarray(new), atol=0.1)


def test_bad_kv_dtype_rejected():
    with pytest.raises(ValueError, match="kv_dtype"):
        CacheCodec("int4")


# ---------------------------------------------------------------------------
# Cache construction (values + scale leaves, real and abstract)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["qwen1.5-0.5b", "deepseek-v3-671b"])
def test_init_cache_int8_structure(name):
    cfg = reduced_cfg(name)
    model = Model(cfg, ModelOptions(kv_dtype="int8"))
    cache = model.init_cache(2, 16)
    abstract = model.init_cache(2, 16, abstract=True)
    vals = cache[0]
    assert vals.dtype == jnp.int8
    scale = cache[2]   # k_scale / c_scale
    assert scale is not None and scale.dtype == jnp.float32
    assert scale.shape == vals.shape[:-1]
    for real, ab in zip(jax.tree.leaves(cache), jax.tree.leaves(abstract)):
        assert (real.shape, real.dtype) == (ab.shape, ab.dtype)


def test_init_cache_int8_rejects_recurrent_families():
    cfg = reduced_cfg("falcon-mamba-7b")
    with pytest.raises(ValueError, match="kv_dtype='int8' is unsupported"):
        Model(cfg, ModelOptions(kv_dtype="int8")).init_cache(2, 16)


# ---------------------------------------------------------------------------
# Decode-equivalence tolerance sweeps (GQA and MLA, dense and paged)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["qwen1.5-0.5b", "deepseek-v3-671b"])
def test_int8_cache_decode_tracks_float_cache(name):
    """prefill + token-by-token decode with the int8 cache stays within
    quantization tolerance of the float cache at every step."""
    cfg = reduced_cfg(name)
    fm = Model(cfg, ModelOptions(kv_dtype="compute"))
    qm = Model(cfg, ModelOptions(kv_dtype="int8"))
    params = fm.init(jax.random.PRNGKey(0))
    S, P = 12, 5
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0,
                              cfg.vocab_size)
    pb = {"tokens": toks[:, :P]}
    lg_f, cache_f = fm.prefill(params, pb, max_len=S)
    lg_q, cache_q = qm.prefill(params, pb, max_len=S)
    scale = float(jnp.abs(lg_f).max()) + 1e-6
    assert float(jnp.max(jnp.abs(lg_q - lg_f))) < 3e-2 * scale
    for t in range(P, S):
        lf, cache_f = fm.decode_step(params, cache_f, toks[:, t:t + 1],
                                     jnp.int32(t))
        lq, cache_q = qm.decode_step(params, cache_q, toks[:, t:t + 1],
                                     jnp.int32(t))
        err = float(jnp.max(jnp.abs(lq - lf)))
        assert err < 3e-2 * scale, f"{name} step {t}: {err}"


@pytest.mark.parametrize("name", ["qwen1.5-0.5b", "deepseek-v3-671b"])
def test_int8_paged_decode_tracks_float_dense(name):
    """Paged int8 decode (block-table gather + scale gather) stays within
    quantization tolerance of the float dense cache."""
    from repro.core.paging import PagingConfig
    cfg = reduced_cfg(name)
    fm = Model(cfg, ModelOptions(kv_dtype="compute"))
    qm = Model(cfg, ModelOptions(kv_dtype="int8"))
    params = fm.init(jax.random.PRNGKey(0))
    B, S, bs = 2, 16, 4
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                              cfg.vocab_size)
    cache_f = fm.init_cache(B, S)
    cache_q = qm.init_cache(B, S, paging=PagingConfig(block_size=bs,
                                                      num_blocks=B * S // bs))
    # disjoint physical blocks per slot (block 0 is the null block)
    tables = jnp.arange(1, 1 + B * (S // bs), dtype=jnp.int32) \
        .reshape(B, S // bs)
    step_f = jax.jit(lambda c, t, i: fm.decode_step(params, c, t, i))
    step_q = jax.jit(lambda c, t, i: qm.decode_step(params, c, t, i,
                                                    block_tables=tables))
    # the MoE model's top-k router can flip an expert choice under the
    # codec's perturbation — a discontinuous (but bounded) logit jump
    tol = 8e-2 if cfg.moe is not None else 3e-2
    scale = None
    for t in range(S):
        lf, cache_f = step_f(cache_f, toks[:, t:t + 1], jnp.int32(t))
        lq, cache_q = step_q(cache_q, toks[:, t:t + 1], jnp.int32(t))
        scale = scale or float(jnp.abs(lf).max()) + 1e-6
        assert float(jnp.max(jnp.abs(lq - lf))) < tol * scale, t


# ---------------------------------------------------------------------------
# Serving-mode stream identity (the test-size models move no argmax)
# ---------------------------------------------------------------------------
# per-arch workloads chosen free of argmax near-ties under the codec's
# <0.5% per-row error (verified across every layout/scheduler variant)
PROMPTS = {
    "qwen1.5-0.5b": [[1, 2, 3], [4, 5, 6, 7, 8, 9], [7] * 12, [30, 31]],
    "deepseek-v3-671b": [[1, 2, 3], [2, 4, 6, 8], [7] * 12, [30, 31]],
}


def _serve(cfg, params, kv_dtype, layout="dense", policy="auto",
           impl="gather", max_new=6, compute_dtype="bf16"):
    spec = RuntimeSpec(
        arch=cfg,
        execution=ExecutionSpec(paged_attn_impl=impl,
                                compute_dtype=compute_dtype),
        memory=MemorySpec(cache_layout=layout, max_batch=4, max_len=64,
                          block_size=8, kv_dtype=kv_dtype),
        scheduler=SchedulerSpec(policy=policy))
    eng = ServingEngine(spec, sampling=SamplingParams())
    eng.load(params)
    prompts = PROMPTS[cfg.name]
    uids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    done = {r.uid: r.generated for r in eng.run_to_completion()}
    return [done[u] for u in uids], eng


@pytest.mark.parametrize("name", ["qwen1.5-0.5b", "deepseek-v3-671b"])
def test_int8_cache_streams_match_float(name):
    cfg = reduced_cfg(name)
    params = Model(cfg).init(jax.random.PRNGKey(0))
    base, _ = _serve(cfg, params, "compute")
    for kwargs in ({}, {"layout": "paged"}, {"policy": "bucketed"},
                   {"layout": "paged", "policy": "bucketed"}):
        got, eng = _serve(cfg, params, "int8", **kwargs)
        assert got == base, kwargs
        if kwargs.get("policy") != "bucketed":
            assert eng.compilations["decode"] == 1
            assert eng.compilations["prefill"] == 1


def test_int8_cache_pallas_kernels_match_gather():
    """The fused Pallas paged-decode and chunked-prefill kernels consume
    the int8 pool + scales through the block-table walk and serve the
    streams the gather path serves from the same int8 pool.  Both run in
    float32: in bf16 the kernels' f32 softmax and the gather path's bf16
    one part by ~1% of the logit scale, with a bf16 pool as with an int8
    one, which flips near-tied argmaxes of the tiny random model."""
    cfg = reduced_cfg("qwen1.5-0.5b")
    params = Model(cfg).init(jax.random.PRNGKey(0))
    base, _ = _serve(cfg, params, "int8", layout="paged",
                     compute_dtype="fp32")
    got, eng = _serve(cfg, params, "int8", layout="paged", impl="pallas",
                      compute_dtype="fp32")
    assert got == base
    assert eng.compilations["decode"] == 1


# ---------------------------------------------------------------------------
# Fully-quantized fleet: int8 weight table + int8 cache, ONE compiled step
# ---------------------------------------------------------------------------
CFG_A = reduced_cfg("qwen1.5-0.5b")
CFG_B = dataclasses.replace(
    CFG_A, name="adaptor-bert-shaped", num_layers=1, d_model=48,
    num_heads=3, num_kv_heads=3, d_ff=96, vocab_size=96)
MAXIMA = maxima_for(CFG_A, CFG_B, seq_max=64)
# prompts chosen to carry no argmax near-tie under int8 weight + cache
# quantization (verified stable across every codec/weight combination)
FLEET_PROMPTS_A = [list(range(1, 12)), [10, 20, 30, 40], [5, 9, 14]]
FLEET_PROMPTS_B = [[4, 5], [6, 7, 8], [80, 70, 60, 50]]
MAX_NEW = 5


def _fleet_params():
    return (Model(CFG_A).init(jax.random.PRNGKey(0)),
            Model(CFG_B).init(jax.random.PRNGKey(1)))


def _fleet(pa, pb, quant, kv_dtype, layout="dense", impl="gather"):
    spec = RuntimeSpec(
        arch=CFG_A, maxima=MAXIMA,
        execution=ExecutionSpec(quant=quant, quant_min_size=1,
                                paged_attn_impl=impl),
        memory=MemorySpec(cache_layout=layout, max_batch=4, max_len=64,
                          block_size=8, kv_dtype=kv_dtype))
    eng = ServingEngine(spec, max_models=2, sampling=SamplingParams())
    a = eng.add_model(pa, CFG_A)
    b = eng.add_model(pb, CFG_B)
    uid_to = {}
    for name, mid, plist in (("a", a, FLEET_PROMPTS_A),
                             ("b", b, FLEET_PROMPTS_B)):
        for p in plist:
            uid = eng.submit(p, max_new_tokens=MAX_NEW, model=mid)
            uid_to[uid] = (name, tuple(p))
    done = eng.run_to_completion()
    return {uid_to[r.uid]: r.generated for r in done}, eng


def _solo_all(pa, pb, quant, kv_dtype):
    out = {}
    for name, cfg, params, plist in (("a", CFG_A, pa, FLEET_PROMPTS_A),
                                     ("b", CFG_B, pb, FLEET_PROMPTS_B)):
        spec = RuntimeSpec(
            arch=cfg,
            execution=ExecutionSpec(quant=quant, quant_min_size=1),
            memory=MemorySpec(max_batch=4, max_len=64, kv_dtype=kv_dtype))
        eng = ServingEngine(spec, sampling=SamplingParams())
        eng.load(params)
        uid_to = {eng.submit(p, max_new_tokens=MAX_NEW): (name, tuple(p))
                  for p in plist}
        out |= {uid_to[r.uid]: r.generated for r in eng.run_to_completion()}
    return out


def test_quantized_fleet_serves_mixed_workload():
    """The acceptance bar: RuntimeSpec(memory=MemorySpec(kv_dtype='int8'),
    execution=ExecutionSpec(quant='int8'), maxima=...) serves a mixed
    fleet end-to-end from ONE compiled decode step, with greedy streams
    matching the float-cache single-topology baselines."""
    pa, pb = _fleet_params()
    mixed, eng = _fleet(pa, pb, "int8", "int8")
    assert eng.compilations["decode"] == 1
    assert eng.compilations["prefill"] == 1
    # the float-cache, float-weight single-topology baseline
    float_base = _solo_all(pa, pb, "none", "compute")
    assert mixed == float_base
    # and the fully-quantized single-topology engines agree too
    assert mixed == _solo_all(pa, pb, "int8", "int8")


def test_quantized_fleet_paged_matches_dense():
    pa, pb = _fleet_params()
    dense, _ = _fleet(pa, pb, "int8", "int8")
    paged, eng = _fleet(pa, pb, "int8", "int8", layout="paged")
    assert paged == dense
    assert eng.compilations["decode"] == 1


def test_quantized_fleet_pallas_kernel_smoke():
    """int8 pool + scales through the fabric's Pallas block-table kernels
    (padded-head-lane masking) must run the fleet to completion with one
    compilation and in-vocab tokens."""
    pa, pb = _fleet_params()
    got, eng = _fleet(pa, pb, "int8", "int8", layout="paged", impl="pallas")
    assert eng.compilations["decode"] == 1
    for (name, _), toks in got.items():
        assert len(toks) == MAX_NEW
        vocab = CFG_B.vocab_size if name == "b" else CFG_A.vocab_size
        assert all(0 <= t < vocab for t in toks)


def test_fleet_int8_table_is_actually_quantized():
    """add_model packs int8 values + scales (not silently float)."""
    from repro.core.quant import QTensor
    pa, _ = _fleet_params()
    _, eng = _fleet(pa, Model(CFG_B).init(jax.random.PRNGKey(1)),
                    "int8", "int8")
    assert isinstance(eng.params["embed"], QTensor)
    wq = eng.params["layers"]["wq"]
    assert isinstance(wq, QTensor) and wq.values.dtype == jnp.int8
    assert eng.cache.k.dtype == jnp.int8
    assert eng.cache.k_scale is not None


# ---------------------------------------------------------------------------
# quant_min_size threading
# ---------------------------------------------------------------------------
def test_quant_min_size_threads_through_engine_load():
    from repro.core.quant import QTensor

    def n_qtensors(tree):
        return sum(1 for l in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, QTensor))
            if isinstance(l, QTensor))

    cfg = reduced_cfg("qwen1.5-0.5b")
    params = Model(cfg).init(jax.random.PRNGKey(0))
    few = ServingEngine(RuntimeSpec(
        arch=cfg, execution=ExecutionSpec(quant="int8"),
        memory=MemorySpec(max_batch=2, max_len=32)))
    few.load(params)
    many = ServingEngine(RuntimeSpec(
        arch=cfg, execution=ExecutionSpec(quant="int8", quant_min_size=1),
        memory=MemorySpec(max_batch=2, max_len=32)))
    many.load(params)
    # the default floor (65536 elements) leaves the reduced model's tiny
    # kernels in float; floor 1 quantizes all of them
    assert n_qtensors(few.params) < n_qtensors(many.params)
    assert n_qtensors(many.params) >= 5
