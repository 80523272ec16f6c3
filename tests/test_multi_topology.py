"""Multi-topology serving: ONE compiled decode step, a mixed model fleet.

The acceptance bar for the register-driven fabric:

* a fleet engine serving two differently-shaped models concurrently
  produces token streams bit-identical to two single-topology engines,
* with exactly one decode compilation (zero retraces after warmup),
* in both cache layouts (dense rows and the paged pool),
* and the fabric's masked math matches the zoo ``Model`` numerically.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_cfg
from repro.core.spec import MemorySpec, RuntimeSpec, maxima_for
from repro.kernels.runtime import interpret_default
from repro.models.model import Model
from repro.serving.engine import ServingEngine
from repro.serving.fabric import DecodeFabric
from repro.serving.sampling import SamplingParams

# Member A: qwen1.5-0.5b-shaped (reduced).  Member B: a smaller, odd-headed
# topology standing in for an adaptor-bert-shaped fleet member — same
# structural template (rmsnorm / swiglu / rope / head_dim 16), different
# registers on every axis the fabric adapts over.
CFG_A = reduced_cfg("qwen1.5-0.5b")
CFG_B = dataclasses.replace(
    CFG_A, name="adaptor-bert-shaped", num_layers=1, d_model=48,
    num_heads=3, num_kv_heads=3, d_ff=96, vocab_size=96)
MAXIMA = maxima_for(CFG_A, CFG_B, seq_max=64)

PROMPTS_A = [[1, 2, 3], list(range(1, 12)), [7, 7, 7]]
PROMPTS_B = [[4, 5], list(range(2, 20, 2))]


def _params():
    return (Model(CFG_A).init(jax.random.PRNGKey(0)),
            Model(CFG_B).init(jax.random.PRNGKey(1)))


def _engine(cache_layout="dense", **mem_kw):
    spec = RuntimeSpec(arch=CFG_A, maxima=MAXIMA,
                       memory=MemorySpec(cache_layout=cache_layout,
                                         max_batch=4, max_len=64,
                                         block_size=8, **mem_kw))
    return ServingEngine(spec, max_models=2, sampling=SamplingParams())


def _run_fleet(eng, params_a, params_b, only=None):
    """Submit the standard mixed workload (or one side of it); returns
    {(model_name, prompt): generated}."""
    ids = {}
    if only in (None, "a"):
        ids["a"] = eng.add_model(params_a, CFG_A)
    if only in (None, "b"):
        ids["b"] = eng.add_model(params_b, CFG_B)
    want = []
    if "a" in ids:
        want += [("a", p) for p in PROMPTS_A]
    if "b" in ids:
        want += [("b", p) for p in PROMPTS_B]
    # interleave submissions so fleet members genuinely share batches
    uid_to_key = {}
    for name, p in sorted(want, key=lambda kp: len(kp[1])):
        uid = eng.submit(p, max_new_tokens=6, model=ids[name])
        uid_to_key[uid] = (name, tuple(p))
    done = eng.run_to_completion()
    assert len(done) == len(want)
    return {uid_to_key[r.uid]: r.generated for r in done}


# ---------------------------------------------------------------------------
# The headline claim
# ---------------------------------------------------------------------------
def test_mixed_fleet_bit_identical_to_single_topology_engines():
    params_a, params_b = _params()
    eng_ab = _engine()
    mixed = _run_fleet(eng_ab, params_a, params_b)
    # zero retraces after warmup: one fused mixed step serves both
    # topologies' prefill AND decode (chunked scheduler — no bucketed
    # prefill dispatch exists anymore)
    assert eng_ab.compilations["decode"] == 1
    assert eng_ab.compilations["prefill"] == 1
    assert eng_ab.compilations["prefill_buckets"] == 0

    solo_a = _run_fleet(_engine(), params_a, params_b, only="a")
    solo_b = _run_fleet(_engine(), params_a, params_b, only="b")
    solo = {**solo_a, **solo_b}
    assert set(mixed) == set(solo)
    for key in mixed:
        assert mixed[key] == solo[key], key


def test_paged_fleet_matches_dense_fleet():
    params_a, params_b = _params()
    dense = _run_fleet(_engine(), params_a, params_b)
    for num_blocks in (None, 14):   # worst-case pool / undersized pool
        eng = _engine("paged", num_blocks=num_blocks)
        paged = _run_fleet(eng, params_a, params_b)
        assert paged == dense, num_blocks
        assert eng.compilations["decode"] == 1


def test_pallas_paged_attn_fleet_smoke():
    """The flash-decode kernel path (padded-head-lane masking) must run
    the mixed fleet to completion with zero retraces."""
    from repro.core.spec import ExecutionSpec
    params_a, params_b = _params()
    spec = RuntimeSpec(arch=CFG_A, maxima=MAXIMA,
                       execution=ExecutionSpec(paged_attn_impl="pallas"),
                       memory=MemorySpec(cache_layout="paged", max_batch=2,
                                         max_len=64, block_size=8))
    eng = ServingEngine(spec, max_models=2, sampling=SamplingParams())
    a = eng.add_model(params_a, CFG_A)
    b = eng.add_model(params_b, CFG_B)
    ua = eng.submit([1, 2, 3], max_new_tokens=3, model=a)
    ub = eng.submit([4, 5], max_new_tokens=3, model=b)
    done = {r.uid: r for r in eng.run_to_completion()}
    assert len(done[ua].generated) == 3 and len(done[ub].generated) == 3
    assert all(0 <= t < CFG_B.vocab_size for t in done[ub].generated)
    assert eng.compilations["decode"] == 1


# ---------------------------------------------------------------------------
# Fabric math vs the zoo Model (oracle)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cfg,seed", [(CFG_A, 0), (CFG_B, 1)])
def test_fabric_matches_zoo_model_numerically(cfg, seed):
    """Padded maximal compute + registers == the dedicated unpadded model,
    through prefill AND several decode steps (the idle lanes of the
    fabric never contaminate live lanes)."""
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    fab = DecodeFabric(MAXIMA, 1, cfg)
    table = fab.insert_model(fab.init_table(), fab.pack_member(cfg, params),
                             0)
    topo = jnp.asarray(fab.topo_row(cfg, 0), jnp.int32)

    prompt = [1, 2, 3, 4, 5]
    toks = jnp.asarray([prompt + [0] * (16 - len(prompt))], jnp.int32)
    max_len = 32
    lg_f, cache_f = fab.prefill(table, topo, toks, max_len)
    lg_m, cache_m = model.prefill(params, {"tokens": toks}, max_len=max_len)
    v = cfg.vocab_size
    np.testing.assert_allclose(
        np.asarray(lg_f[:, :len(prompt), :v]),
        np.asarray(lg_m[:, :len(prompt)]), atol=5e-2, rtol=5e-2)

    tok = int(jnp.argmax(lg_m[0, len(prompt) - 1]))
    idx = len(prompt)
    for _ in range(3):
        t = jnp.asarray([[tok]], jnp.int32)
        lg_f, cache_f = fab.decode_step(table, cache_f, t,
                                        jnp.asarray([idx], jnp.int32),
                                        topo[None],
                                        interpret=interpret_default())
        lg_m, cache_m = model.decode_step(params, cache_m, t, jnp.int32(idx))
        np.testing.assert_allclose(np.asarray(lg_f[:, :, :v]),
                                   np.asarray(lg_m), atol=5e-2, rtol=5e-2)
        # dead vocab lanes must be unsampleable
        assert v == lg_f.shape[-1] or float(jnp.max(lg_f[:, :, v:])) < -1e30
        tok = int(jnp.argmax(lg_m[0, 0]))
        idx += 1


# ---------------------------------------------------------------------------
# Fleet admission errors (actionable, at load/submit time)
# ---------------------------------------------------------------------------
def test_structural_mismatch_rejected():
    params_a, _ = _params()
    eng = _engine()
    eng.add_model(params_a, CFG_A)
    wrong_norm = dataclasses.replace(CFG_A, name="ln-model", norm="layernorm")
    with pytest.raises(ValueError, match="frozen at compile"):
        eng.add_model(params_a, wrong_norm)
    too_big = dataclasses.replace(CFG_A, name="big", d_model=128, d_ff=256)
    with pytest.raises(ValueError, match="re-synthesis"):
        eng.add_model(params_a, too_big)


def test_submit_unloaded_model_rejected():
    params_a, _ = _params()
    eng = _engine()
    eng.add_model(params_a, CFG_A)
    with pytest.raises(ValueError, match="not loaded"):
        eng.submit([1, 2], model=1)
    with pytest.raises(ValueError, match="vocab"):
        eng.submit([CFG_A.vocab_size + 5], model=0)


def test_single_topology_submit_rejects_model_kwarg():
    model = Model(CFG_A)
    eng = ServingEngine(model, max_batch=2, max_len=32)
    eng.load(model.init(jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="multi-topology"):
        eng.submit([1, 2], model=1)
