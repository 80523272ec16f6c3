"""The serving engine's own marks for a profiler and for event readers.

* the mixed and decode step programs carry every ``SCOPE_NAMES`` entry
  in their compiled HLO ``op_name`` metadata (GQA and MLA paged paths;
  the ``moe.*`` scopes where the model has experts);
* one ``dispatch`` event per step program launched, with the grants,
  lanes and live lanes of that launch (and relayed by a cluster with
  its replica);
* an MoE model's ``experts`` event per harvest holds what a host recount
  of the routing gives;
* under ``jax.profiler`` the engine's phase spans nest under
  ``engine.step`` and carry their kwargs.
"""
import dataclasses
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_cfg
from repro.core.spec import MemorySpec, MeshSpec, RuntimeSpec, SchedulerSpec
from repro.models import moe
from repro.models.model import Model
from repro.serving.cluster import EngineCluster
from repro.serving.engine import ServingEngine
from repro.serving.events import SCOPE_NAMES, SPAN_NAMES, EventLog
from repro.serving.sampling import SamplingParams

PROMPTS = [[1, 2, 3], list(range(1, 30)), [4, 5], list(range(3, 20)),
           [9] * 12]


def _spec(cfg, policy="chunked", mesh=None, **sched):
    return RuntimeSpec(
        arch=cfg, mesh=mesh or MeshSpec(),
        memory=MemorySpec(cache_layout="paged", max_batch=4, max_len=64,
                          block_size=8),
        scheduler=SchedulerSpec(policy=policy, **sched))


def _engine(name="qwen1.5-0.5b", policy="chunked", cfg=None, **sched):
    cfg = cfg or reduced_cfg(name)
    eng = ServingEngine(_spec(cfg, policy, **sched),
                        sampling=SamplingParams())
    eng.load(Model(cfg).init(jax.random.PRNGKey(0)))
    return eng


@pytest.mark.parametrize("program", ["mixed", "decode"])
@pytest.mark.parametrize("name", ["qwen1.5-0.5b", "deepseek-v3-671b"])
def test_step_programs_carry_every_scope(name, program):
    eng = _engine(name)
    args = (eng.params, eng.cache, eng.state, eng.block_tables)
    if program == "mixed":
        lowered = eng._step.lower(*args, jnp.ones((eng.max_batch,),
                                                  jnp.int32))
    else:
        lowered = eng._decode.lower(*args)
    text = lowered.compile().as_text()
    parts = {p for op in re.findall(r'op_name="([^"]*)"', text)
             for p in op.split("/")}
    want = {sc for sc in SCOPE_NAMES
            if eng.model.cfg.moe is not None or not sc.startswith("moe.")}
    assert want <= parts, want - parts


def _drain(eng):
    log = EventLog()
    eng.events.subscribe(log)
    grants = []
    grant = eng._grant_chunks

    def recorded():
        g = grant()
        grants.append(g)
        return g

    eng._grant_chunks = recorded
    for i, p in enumerate(PROMPTS):
        eng.submit(p, max_new_tokens=3 + i)
    while eng.queue or any(r is not None for r in eng.slot_req):
        eng.step()
    return log.events, grants


@pytest.mark.parametrize("policy", ["chunked", "bucketed"])
def test_one_dispatch_event_per_launch(policy):
    eng = _engine(policy=policy, chunk_size=8, token_budget=12)
    events, grants = _drain(eng)
    disp = [e for e in events if e.kind == "dispatch"]
    assert len(disp) == eng.stats["decode_steps"]
    assert [e.step for e in disp] == list(range(1, len(disp) + 1))
    assert all(e.uid == -1 for e in disp)
    if policy == "chunked":
        assert [e.data["prefill_tokens"] for e in disp] == \
            [sum(g) for g in grants]
        assert any(e.data["program"] == "mixed" for e in disp)
    # a request decodes in the dispatches after the one that sampled its
    # first token, up to the one harvested with its finish
    first = {e.uid: e.step for e in events if e.kind == "first_token"}
    fin = {e.uid: e.step for e in events if e.kind == "finish"}
    for e in disp:
        d = e.data
        mixed = d["prefill_tokens"] > 0
        assert d["program"] == ("mixed" if mixed else "decode")
        width = eng.chunk_size if mixed else 1
        assert d["lanes"] == eng.max_batch * width
        decoding = sum(first[u] < e.step <= fin[u] for u in first)
        assert d["live_lanes"] == d["prefill_tokens"] + decoding
        assert 1 <= d["slots"] <= eng.max_batch


def test_cluster_relays_dispatch_with_its_replica():
    cfg = reduced_cfg("qwen1.5-0.5b")
    cl = EngineCluster(_spec(cfg, mesh=MeshSpec(tp=1, dp=2)))
    cl.load(Model(cfg).init(jax.random.PRNGKey(0)))
    log = EventLog()
    cl.events.subscribe(log)
    for p in PROMPTS:
        cl.submit(p, max_new_tokens=3)
    cl.run_to_completion()
    disp = [e for e in log.events if e.kind == "dispatch"]
    steps = [r["decode_steps"] for r in cl.replica_stats()]
    assert [sum(e.data["replica"] == i for e in disp) for i in (0, 1)] \
        == steps
    assert all(e.uid == -1 for e in disp)


def test_engine_spans_nest_under_step(tmp_path):
    from jax.profiler import ProfileData

    eng = _engine()
    eng.submit([1, 2, 3], max_new_tokens=2)     # compile outside the trace
    while eng.slot_req[0] is not None or eng.queue:
        eng.step()
    jax.profiler.start_trace(str(tmp_path))
    eng.submit(list(range(1, 12)), max_new_tokens=3)
    while eng.queue or any(r is not None for r in eng.slot_req):
        eng.step()
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[-1]
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
              dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("engine.")]
    names = {n for n, *_ in spans}
    assert names <= set(SPAN_NAMES)
    assert {"engine.submit", "engine.step", "engine.admit",
            "engine.capacity", "engine.dispatch", "engine.harvest",
            "engine.harvest.wait", "engine.harvest.fetch"} <= names

    def inside(n, parent):
        outer = [(s, e) for m, s, e, _ in spans if m == parent]
        return all(any(ps <= s and e <= pe for ps, pe in outer)
                   for m, s, e, _ in spans if m == n)

    for phase in ("engine.admit", "engine.capacity", "engine.dispatch",
                  "engine.harvest"):
        assert inside(phase, "engine.step"), phase
    assert inside("engine.harvest.wait", "engine.harvest")
    assert inside("engine.harvest.fetch", "engine.harvest")
    steps = sorted(int(st["step"]) for n, _, _, st in spans
                   if n == "engine.step")
    assert steps == list(range(steps[0], steps[0] + len(steps)))
    programs = {st["program"] for n, _, _, st in spans
                if n == "engine.dispatch"}
    assert programs == {"mixed", "decode"}


def test_experts_event_matches_a_host_recount_of_the_routing(monkeypatch):
    """A layer holding experts 2-3 of 4: each harvest's ``experts`` event
    gives the (token, expert) pairs of live lanes routed to them and the
    held experts hit, summed over the MoE layers of the step, as the
    routing itself (recorded from inside the step) says."""
    base = reduced_cfg("deepseek-v3-671b")
    cfg = dataclasses.replace(base, num_layers=3, moe=dataclasses.replace(
        base.moe, expert_offset=2, held_experts=2))
    calls = []
    held = moe._held_experts

    def recording(x, top_w, top_i, live, *args):
        jax.debug.callback(
            lambda i, l: calls.append((np.asarray(i), np.asarray(l))),
            top_i, live)
        return held(x, top_w, top_i, live, *args)

    monkeypatch.setattr(moe, "_held_experts", recording)
    eng = _engine(cfg=cfg)
    log = EventLog()
    eng.events.subscribe(log)
    for i, p in enumerate(PROMPTS):
        eng.submit(p, max_new_tokens=3 + i)
    want = []
    while eng.queue or any(r is not None for r in eng.slot_req):
        n0 = len(calls)
        eng.step()
        rows = hit = 0
        for ids, live in calls[n0:]:       # one call per MoE layer
            local = ids[live] - 2
            mine = local[(local >= 0) & (local < 2)]
            rows += mine.size
            hit += len(set(mine.tolist()))
        assert len(calls) - n0 == 2
        want.append((rows, hit))
    got = [(e.data["expert_rows"], e.data["experts_hit"])
           for e in log.events if e.kind == "experts"]
    assert got == want and sum(r for r, _ in got) > 0
    assert all(e.uid == -1 and e.data["dispatches"] == 1
               for e in log.events if e.kind == "experts")
