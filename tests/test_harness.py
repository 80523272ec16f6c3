"""Load harness: seeded traces, hand-computed metrics, exactly-once
lifecycle events across engine modes, and the analytical autotuner.

The metric tests build ``EngineEvent`` lists by hand and check the
reduction against arithmetic done in comments — the definitions in
``repro.harness.metrics`` are only trustworthy if a human can recompute
them.
"""
import dataclasses

import pytest

from conftest import reduced_cfg
from repro.core.spec import (ExecutionSpec, MemorySpec, RuntimeSpec,
                             SchedulerSpec, maxima_for)
from repro.harness import (SLO, DeviceProfile, WorkloadProfile,
                           bursty_trace, fleet_trace, load_trace,
                           poisson_trace, reduce_events, replay, save_trace,
                           scripted_trace, shared_prefix_trace, tune)
from repro.harness.metrics import percentile
from repro.harness.trace import TraceRequest, dumps_trace, loads_trace
from repro.harness.tune import cache_bytes, naive_default
from repro.serving.events import EngineEvent

# ----------------------------------------------------------------------
# traces
# ----------------------------------------------------------------------

_GENERATORS = [
    lambda seed: poisson_trace(12, rate=0.5, max_len=32, max_new=4,
                               seed=seed),
    lambda seed: bursty_trace(12, burst_size=4, gap_steps=6, max_len=32,
                              max_new=4, seed=seed),
    lambda seed: shared_prefix_trace(12, n_families=2, prefix_len=16,
                                     max_len=48, max_new=4, seed=seed),
    lambda seed: fleet_trace(12, n_models=3, max_len=32, max_new=4,
                             seed=seed),
]


@pytest.mark.parametrize("gen", _GENERATORS)
def test_traces_byte_reproducible(gen):
    a, b = gen(7), gen(7)
    assert dumps_trace(a) == dumps_trace(b)
    assert dumps_trace(gen(8)) != dumps_trace(a)


@pytest.mark.parametrize("gen", _GENERATORS)
def test_trace_roundtrip(gen, tmp_path):
    t = gen(3)
    assert loads_trace(dumps_trace(t)) == t
    p = tmp_path / "t.jsonl"
    save_trace(t, p)
    assert load_trace(p) == t


def test_trace_invariants():
    for gen in _GENERATORS:
        t = gen(5)
        assert len(t) == 12
        for r in t.requests:
            assert r.arrival_step >= 0
            assert len(r.prompt) >= 1
            assert r.max_new_tokens >= 1
            assert all(tok >= 1 for tok in r.prompt)   # 0 is the pad id


def test_trace_request_validation():
    with pytest.raises(ValueError):
        TraceRequest(rid=0, arrival_step=-1, prompt=(1,), max_new_tokens=1)
    with pytest.raises(ValueError):
        TraceRequest(rid=0, arrival_step=0, prompt=(), max_new_tokens=1)
    with pytest.raises(ValueError):
        TraceRequest(rid=0, arrival_step=0, prompt=(1,), max_new_tokens=0)


def test_scripted_trace_preserves_rows():
    t = scripted_trace([(0, [1, 2], 3), (4, [5], 1)], name="toy")
    assert [r.arrival_step for r in t.requests] == [0, 4]
    assert t.requests[0].prompt == (1, 2)
    assert t.requests[1].max_new_tokens == 1


def test_shared_prefix_trace_shares_prefixes():
    t = shared_prefix_trace(10, n_families=2, prefix_len=8, shared_frac=0.8,
                            max_len=32, max_new=4, seed=1)
    prefixes = {}
    shared = 0
    for r in t.requests:
        head = r.prompt[:8]
        if head in prefixes:
            shared += 1
        prefixes[head] = prefixes.get(head, 0) + 1
    assert shared >= 5        # 80% of 10 across 2 families must collide
    assert t.meta["shared_frac"] == 0.8


# ----------------------------------------------------------------------
# metrics: hand-computed on a toy event stream
# ----------------------------------------------------------------------

def _ev(kind, uid, step, t, **data):
    return EngineEvent(kind=kind, uid=uid, step=step, t=t, data=data)


def _toy_events():
    """Three requests; r2 is preempted once and never finishes."""
    return [
        _ev("submit", 0, 0, 0.0), _ev("submit", 1, 0, 0.0),
        _ev("submit", 2, 0, 0.0),
        _ev("admit", 0, 0, 0.0),
        _ev("admit", 1, 1, 1.0), _ev("admit", 2, 1, 1.0),
        _ev("first_token", 0, 1, 0.5),
        _ev("progress", 0, 1, 1.0, count=1),
        _ev("preempt", 2, 2, 2.0, banked=0),
        _ev("first_token", 1, 3, 2.5),
        _ev("progress", 0, 3, 3.0, count=3),
        _ev("progress", 1, 3, 3.0, count=1),
        _ev("finish", 0, 3, 3.0, n_generated=3),
        _ev("admit", 2, 4, 4.0),
        _ev("progress", 1, 4, 4.0, count=2),
        _ev("finish", 1, 4, 4.0, n_generated=2),
        _ev("first_token", 2, 5, 4.5),
        _ev("progress", 2, 5, 5.0, count=1),
    ]


def test_metrics_hand_computed():
    m = reduce_events(_toy_events(), slo=SLO(ttft_steps=2))
    assert m.n_requests == 3
    assert m.n_finished == 2
    assert m.n_preemptions == 1
    # admits: r0@0 -> 1; r1,r2@1 -> 3 (peak); preempt r2 -> 2; ...
    assert m.peak_concurrency == 3
    assert m.steps == 5                      # event steps span 0..5
    # TTFT steps: r0 = 1-0, r1 = 3-0, r2 = 5-0
    assert m.per_request[0]["ttft_steps"] == 1
    assert m.per_request[1]["ttft_steps"] == 3
    assert m.per_request[2]["ttft_steps"] == 5
    # nearest-rank over [1, 3, 5]: p50 -> ceil(1.5)=2nd -> 3; p99 -> 5
    assert m.ttft_steps_p50 == 3
    assert m.ttft_steps_p99 == 5
    # ITL: r0 counts 1@1 -> 3@3 gives 2 samples of (3-1)/2 = 1.0;
    # r1 counts 1@3 -> 2@4 gives 1 sample of 1.0; r2 has no pair
    assert m.per_request[0]["n_itl_samples"] == 2
    assert m.per_request[1]["n_itl_samples"] == 1
    assert m.per_request[2]["n_itl_samples"] == 0
    assert m.itl_steps_p50 == 1.0
    assert m.itl_steps_p99 == 1.0
    # only finished requests generate: 3 + 2 (r2 never finished)
    assert m.total_new_tokens == 5
    assert m.tokens_per_step == 1.0
    # SLO ttft<=2: r0 met (1), r1 finished but ttft 3, r2 unfinished
    assert m.n_slo_met == 1
    assert m.slo_attainment == pytest.approx(1 / 3)
    assert m.goodput_req_per_1k_steps == pytest.approx(1000 * 1 / 5)
    # wall view: TTFT seconds = first count>=1 progress minus submit
    assert m.ttft_s_p50 == pytest.approx(3.0)     # [1.0, 3.0, 5.0]
    assert m.wall_s == pytest.approx(5.0)


def test_metrics_no_slo_means_finished():
    m = reduce_events(_toy_events())
    assert m.n_slo_met == m.n_finished == 2


def test_itl_rebaseline_on_count_decrease():
    # counts 2@s0 -> 1@s2 (preemption rollback: re-baseline, no samples)
    # -> 3@s6: 2 samples of (6-2)/2 = 2.0
    events = [
        _ev("submit", 0, 0, 0.0), _ev("admit", 0, 0, 0.0),
        _ev("progress", 0, 0, 0.0, count=2),
        _ev("progress", 0, 2, 2.0, count=1),
        _ev("progress", 0, 6, 6.0, count=3),
        _ev("finish", 0, 6, 6.0, n_generated=3),
    ]
    m = reduce_events(events)
    assert m.per_request[0]["n_itl_samples"] == 2
    assert m.itl_steps_p50 == 2.0
    assert m.per_request[0]["max_itl_steps"] == 2.0


def test_spec_metrics_hand_computed():
    """Mean accepted draft length from the cumulative ``accepted`` /
    ``spec_steps`` progress counters, including the preemption
    re-baseline (mirrors the ITL count-decrease rule)."""
    events = [
        _ev("submit", 0, 0, 0.0), _ev("admit", 0, 0, 0.0),
        _ev("submit", 1, 0, 0.0), _ev("admit", 1, 0, 0.0),
        # r0 speculates: cumulative counters ride its progress events
        _ev("progress", 0, 1, 1.0, count=3, accepted=2, spec_steps=1),
        # r1 speculates twice and accepts nothing — steps still count
        _ev("progress", 1, 1, 1.0, count=2, accepted=0, spec_steps=2),
        _ev("finish", 1, 1, 1.0, n_generated=2),
        _ev("progress", 0, 2, 2.0, count=7, accepted=5, spec_steps=3),
        # preemption resets the device counters: accepted drops 5 -> 1,
        # so the (5, 3) epoch banks and the new epoch re-baselines
        _ev("preempt", 0, 3, 3.0, banked=0),
        _ev("admit", 0, 4, 4.0),
        _ev("progress", 0, 5, 5.0, count=2, accepted=1, spec_steps=1),
        _ev("finish", 0, 5, 5.0, n_generated=2),
    ]
    m = reduce_events(events)
    # r0 banks (5 acc, 3 steps) at the reset plus its open (1, 1) epoch;
    # r1 adds (0, 2): 6 accepted tokens over 6 speculative steps
    assert m.spec_accepted_tokens == 6
    assert m.spec_steps == 6
    assert m.mean_accepted_len == pytest.approx(1.0)
    # spec fields are step-currency: they ride the deterministic view
    assert m.deterministic()["mean_accepted_len"] == pytest.approx(1.0)


def test_spec_metrics_absent_without_speculation():
    m = reduce_events(_toy_events())
    assert m.spec_accepted_tokens == 0 and m.spec_steps == 0
    assert m.mean_accepted_len is None


def test_percentile_nearest_rank():
    assert percentile([], 50) is None
    assert percentile([4, 1, 3, 2], 50) == 2
    assert percentile([4, 1, 3, 2], 99) == 4
    assert percentile([7], 50) == 7


def test_reduce_events_rejects_empty():
    with pytest.raises(ValueError):
        reduce_events([])


def test_deterministic_view_excludes_wall():
    m = reduce_events(_toy_events())
    d = m.deterministic()
    for k in ("wall_s", "ttft_s_p50", "itl_s_p99", "goodput_req_s",
              "tokens_per_s"):
        assert k not in d
    assert d["steps"] == 5
    # canonical serialization is stable
    assert m.deterministic_json() == m.deterministic_json()


# ----------------------------------------------------------------------
# lifecycle events: exactly once per request, across engine modes
# ----------------------------------------------------------------------

def _engine(cfg, *, layout="dense", policy="bucketed", fleet=False):
    import jax

    from repro.models.model import Model
    from repro.serving.engine import ServingEngine
    from repro.serving.sampling import SamplingParams

    maxima = cfg_b = None
    if fleet:
        cfg_b = dataclasses.replace(cfg, name=cfg.name + "-b", num_layers=1,
                                    d_model=48, num_heads=3, num_kv_heads=3,
                                    d_ff=96, vocab_size=96)
        maxima = maxima_for(cfg, cfg_b, seq_max=64)
    spec = RuntimeSpec(
        arch=cfg, maxima=maxima,
        memory=MemorySpec(cache_layout=layout, max_batch=4, max_len=64,
                          block_size=8),
        scheduler=SchedulerSpec(policy=policy))
    eng = ServingEngine(spec, sampling=SamplingParams(),
                        **({"max_models": 2} if fleet else {}))
    eng.load(Model(cfg).init(jax.random.PRNGKey(0)))
    if fleet:
        eng.add_model(Model(cfg_b).init(jax.random.PRNGKey(1)), cfg_b)
    return eng


_MODES = [("dense", "bucketed", False), ("dense", "chunked", False),
          ("paged", "chunked", False), ("paged", "chunked", True)]


@pytest.mark.parametrize("layout,policy,fleet", _MODES)
def test_lifecycle_events_exactly_once(layout, policy, fleet):
    cfg = reduced_cfg("qwen1.5-0.5b")
    eng = _engine(cfg, layout=layout, policy=policy, fleet=fleet)
    rows = [(0, [1, 2, 3], 2), (0, list(range(1, 13)), 3),
            (1, [4, 5], 2), (2, [6, 7, 8, 9], 2),
            (2, list(range(20, 29)), 3), (4, [9, 8, 7], 2)]
    if fleet:
        rows = [(a, p, n, i % 2) for i, (a, p, n) in enumerate(rows)]
    res = replay(eng, scripted_trace(rows, name="lifecycle"))
    m = res.metrics
    assert m.n_finished == len(rows)
    # one dispatch event (no request) per step program launched
    assert sum(e.kind == "dispatch" for e in res.events) == \
        eng.stats["decode_steps"]
    by_uid = {}
    for e in res.events:
        if e.kind != "dispatch":
            by_uid.setdefault(e.uid, []).append(e)
    assert len(by_uid) == len(rows)
    for uid, evs in by_uid.items():
        kinds = [e.kind for e in evs]
        n_admit, n_preempt = kinds.count("admit"), kinds.count("preempt")
        assert kinds.count("submit") == 1, (uid, kinds)
        assert kinds.count("first_token") == 1, (uid, kinds)
        assert kinds.count("finish") == 1, (uid, kinds)
        assert n_admit - n_preempt == 1, (uid, kinds)
        assert kinds[0] == "submit" and kinds[-1] == "finish"
        # the logical clock never runs backwards within one request
        steps = [e.step for e in evs]
        assert steps == sorted(steps)
    # progress carried every finished request to its budget
    for uid, rec in m.per_request.items():
        assert rec["finished"]
        assert rec["n_generated"] >= 1


def test_replay_deterministic_metrics_across_engines():
    cfg = reduced_cfg("qwen1.5-0.5b")
    trace = bursty_trace(8, burst_size=4, gap_steps=6, max_len=24,
                         max_new=3, seed=13)
    views = []
    for _ in range(2):
        eng = _engine(cfg, layout="paged", policy="chunked")
        views.append(replay(eng, trace).metrics.deterministic_json())
    assert views[0] == views[1]


# ----------------------------------------------------------------------
# tuner
# ----------------------------------------------------------------------

def test_tuned_spec_is_valid_and_within_budget():
    cfg = reduced_cfg("qwen1.5-0.5b")
    device = DeviceProfile(cache_budget_bytes=256 * 1024)
    result = tune(cfg, device, max_len=64)
    spec = result.spec
    assert spec.validate() is spec
    assert cache_bytes(spec) <= device.budget(cfg)
    assert result.ranked[0] is result.best
    scores = [c.score for c in result.ranked]
    assert scores == sorted(scores, reverse=True)
    # deterministic: same inputs, same winner
    again = tune(cfg, device, max_len=64)
    assert again.spec == spec


def test_runtime_spec_tuned_matches_tune():
    cfg = reduced_cfg("qwen1.5-0.5b")
    device = DeviceProfile(cache_budget_bytes=128 * 1024)
    assert RuntimeSpec.tuned(cfg, device, max_len=64) \
        == tune(cfg, device, max_len=64).spec


def test_workload_profile_from_trace_reads_meta():
    t = shared_prefix_trace(16, n_families=2, prefix_len=12, shared_frac=0.8,
                            max_len=48, max_new=4, seed=2)
    w = WorkloadProfile.from_trace(t)
    assert w.shared_prefix_frac == 0.8
    assert w.shared_prefix_len == 12
    assert w.max_prompt_len == t.max_prompt_len
    assert w.effective_prompt_len < w.mean_prompt_len


def test_naive_default_pays_equal_bytes():
    cfg = reduced_cfg("qwen1.5-0.5b")
    tuned = tune(cfg, DeviceProfile(cache_budget_bytes=256 * 1024),
                 max_len=64).spec
    naive = naive_default(cfg, tuned)
    assert naive.memory.cache_layout == "dense"
    assert cache_bytes(naive) <= cache_bytes(tuned)
    # within one max_len row of equality — the definition of "equal memory"
    per_row = cache_bytes(naive) // naive.memory.max_batch
    assert cache_bytes(tuned) - cache_bytes(naive) < per_row


def test_tune_int8_kv_is_opt_in():
    cfg = reduced_cfg("qwen1.5-0.5b")
    device = DeviceProfile(cache_budget_bytes=128 * 1024)
    assert tune(cfg, device, max_len=64).spec.memory.kv_dtype == "compute"
    specs = [c.spec for c in
             tune(cfg, device, max_len=64, allow_int8_kv=True).ranked]
    assert any(s.memory.kv_dtype == "int8" for s in specs)


def test_fleet_cache_accounting_matches_fabric():
    from repro.harness.tune import _per_token_bytes
    from repro.serving.fabric import DecodeFabric

    cfg = reduced_cfg("qwen1.5-0.5b")
    cfg_b = dataclasses.replace(cfg, name=cfg.name + "-b", num_layers=1,
                                d_model=48, num_heads=3, num_kv_heads=3,
                                d_ff=96, vocab_size=96)
    maxima = maxima_for(cfg, cfg_b, seq_max=64)
    fab = DecodeFabric(maxima, 2, cfg)
    # one yardstick: the tuner's fleet bytes/token IS the fabric's
    assert _per_token_bytes(cfg, "compute", maxima) \
        == fab.kv_bytes_per_token()
    # maxima-shaped rows cost at least the biggest member's own rows
    assert _per_token_bytes(cfg, "compute", maxima) \
        >= _per_token_bytes(cfg, "compute", None)
    budget = 512 * 1024
    result = tune(cfg, DeviceProfile(cache_budget_bytes=budget),
                  max_len=64, maxima=maxima)
    assert result.spec.maxima is maxima
    assert cache_bytes(result.spec) <= budget


def test_tune_rejects_unsupported_family():
    cfg = reduced_cfg("falcon-mamba-7b")
    with pytest.raises(ValueError):
        tune(cfg, DeviceProfile(cache_budget_bytes=128 * 1024), max_len=64)
