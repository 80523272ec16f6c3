"""Mesh-sharded serving: TP/DP equivalence, capacity planning, placement.

The contract under test is the ISSUE-9 tentpole: the fused mixed step
lowered onto a (1, tp) GSPMD mesh and data-parallel engine replicas
behind one admission queue must stream *bit-identical* tokens to the
historical single-device engine, while keeping the compile-once
discipline (one mixed-step compilation per replica).  All runs use
fp32 compute so cross-device reduction order cannot flip an argmax.
"""
import dataclasses

import jax
import pytest

from conftest import reduced_cfg
from repro.core.spec import (ExecutionSpec, MemorySpec, MeshSpec,
                             RuntimeSpec, SchedulerSpec)
from repro.distributed import sharding as shd
from repro.harness import poisson_trace, replay
from repro.models.model import Model
from repro.serving.cluster import EngineCluster
from repro.serving.engine import ServingEngine

CFG = reduced_cfg("qwen1.5-0.5b")


@pytest.fixture(scope="module")
def params():
    return Model(CFG).init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def trace():
    # staggered arrivals: admissions, slot reuse, and steady-state decode
    # all occur (the all-at-once smoke only exercises admission)
    return poisson_trace(10, rate=0.5, max_len=16, max_new=6,
                         vocab=CFG.vocab_size - 1, seed=3)


def _spec(mesh=MeshSpec(), **mem_kw):
    kw = dict(cache_layout="paged", max_batch=4, max_len=64, block_size=8)
    kw.update(mem_kw)
    return RuntimeSpec(arch=CFG, execution=ExecutionSpec(compute_dtype="fp32"),
                       memory=MemorySpec(**kw), mesh=mesh)


def _streams(engine, trace):
    r = replay(engine, trace)
    return {r.uid_to_rid[q.uid]: tuple(q.generated) for q in r.finished}, r


MATRIX = {
    "dense": dict(cache_layout="dense", scheduler=True),
    "paged": {},
    "int8-kv": dict(kv_dtype="int8"),
    "prefix-cache": dict(prefix_cache=True),
}


@pytest.mark.parametrize("point", sorted(MATRIX))
def test_tp2_streams_bit_identical_to_single_device(point, params, trace):
    mem = dict(MATRIX[point])
    sched = mem.pop("scheduler", False)
    sched_kw = {}
    if sched:
        # dense layout resolves policy 'auto' to bucketed; tp > 1 needs
        # the fused chunked step, so pin it explicitly
        sched_kw["scheduler"] = SchedulerSpec(policy="chunked")

    def build(mesh):
        spec = dataclasses.replace(_spec(mesh=mesh, **mem), **sched_kw)
        eng = ServingEngine(spec)
        eng.load(params)
        return eng

    base, _ = _streams(build(MeshSpec()), trace)
    eng2 = build(MeshSpec(tp=2))
    got, _ = _streams(eng2, trace)
    assert got == base
    comp = eng2.compilations
    assert comp["prefill"] == 1 and comp["decode"] == 1


def test_dp2_cluster_streams_bit_identical_and_events_merge(params, trace):
    base_eng = ServingEngine(_spec())
    base_eng.load(params)
    base, rb = _streams(base_eng, trace)

    cl = EngineCluster(_spec(mesh=MeshSpec(tp=1, dp=2)))
    cl.load(params)
    got, rc = _streams(cl, trace)
    assert got == base
    # every replica kept the compile-once discipline
    for comp in cl.compilations:
        assert comp["prefill"] == 1 and comp["decode"] == 1
    # merged EventLog: every request's full lifecycle under cluster uids,
    # and each replica's dispatches under its index
    req_events = [e for e in rc.events if e.kind != "dispatch"]
    uids = {e.uid for e in req_events}
    assert uids == set(rc.uid_to_rid)
    assert sorted({e.data["replica"] for e in rc.events
                   if e.kind == "dispatch"}) == [0, 1]
    for uid in uids:
        kinds = [e.kind for e in req_events if e.uid == uid]
        assert kinds[0] == "submit" and kinds[-1] == "finish"
        assert "admit" in kinds and "first_token" in kinds
    # the reduced metrics see the same completions as the single engine
    assert len(rc.metrics.per_request) == len(rb.metrics.per_request)


def test_tp2_dp2_cluster_matches_single_device(params, trace):
    base_eng = ServingEngine(_spec())
    base_eng.load(params)
    base, _ = _streams(base_eng, trace)

    cl = EngineCluster(_spec(mesh=MeshSpec(tp=2, dp=2)))
    cl.load(params)
    got, _ = _streams(cl, trace)
    assert got == base


def test_cluster_routes_by_free_capacity(params):
    cl = EngineCluster(_spec(mesh=MeshSpec(tp=1, dp=2)))
    cl.load(params)
    # equal capacity: first submit ties -> replica 0; the second must go
    # to replica 1 (replica 0 now has queued demand)
    cl.submit([1, 2, 3], max_new_tokens=4)
    cl.submit([4, 5, 6], max_new_tokens=4)
    assert len(cl.replicas[0].queue) == 1
    assert len(cl.replicas[1].queue) == 1
    done = cl.run_to_completion()
    # cluster uids are cluster-level (1, 2), not per-replica (1, 1)
    assert sorted(r.uid for r in done) == [1, 2]


def test_capacity_planner_matches_admission(params):
    spec = _spec(mesh=MeshSpec(tp=2, dp=2), max_batch=2)
    cap = spec.capacity()
    assert cap.n_devices == 4
    assert cap.max_concurrent == 4          # dp * max_batch
    assert cap.kv_shards == 2               # 4 kv heads / tp=2
    assert cap.per_device_cache_bytes * cap.kv_shards \
        == cap.cache_bytes_per_replica

    cl = EngineCluster(spec)
    cl.load(params)
    # long decodes hold their slots: admission must seat exactly
    # max_concurrent requests and queue the rest
    for i in range(cap.max_concurrent + 2):
        cl.submit([1 + i, 2, 3], max_new_tokens=32)
    cl.step()
    seated = sum(r is not None for r in cl.slot_req)
    assert seated == cap.max_concurrent
    assert len(cl.queue) == 2


def test_maxima_for_is_mesh_aware():
    from repro.core.registers import Maxima
    from repro.core.spec import maxima_for
    maxima = maxima_for(CFG, seq_max=64)
    sharded = maxima_for(CFG, seq_max=64, mesh=MeshSpec(tp=2))
    assert isinstance(maxima, Maxima)
    # per-device register ceilings halve along every tp-sharded axis
    assert sharded.heads_max * 2 == maxima.heads_max
    assert sharded.d_ff_max * 2 == maxima.d_ff_max


def test_tp2_cache_actually_sharded(params):
    eng = ServingEngine(_spec(mesh=MeshSpec(tp=2)))
    eng.load(params)
    k = jax.tree.leaves(eng.cache)[0]
    # the pool's kv-major row (-1, n_kv * hd) is split over the model
    # axis: each device holds half the heads, and the global shape is
    # unchanged
    shard = k.addressable_shards[0].data
    assert k.shape[-1] == CFG.num_kv_heads * CFG.resolved_head_dim
    assert shard.shape[-1] * 2 == k.shape[-1]
    assert len(k.sharding.device_set) == 2


def test_mesh_divisibility_falls_back_to_replication():
    # 3 kv heads on a tp=2 mesh cannot shard: capacity must report one
    # shard, and the cache sharding helper must replicate the leaf
    odd = dataclasses.replace(CFG, num_heads=3, num_kv_heads=3)
    assert MeshSpec(tp=2).kv_shards(odd) == 1

    devs = jax.devices()[:2]
    mesh = shd.tp_mesh(devs)
    strategy = shd.strategy_for_mesh(mesh)
    import collections
    KV = collections.namedtuple("KV", ["k", "v"])
    import jax.numpy as jnp
    cache = [KV(jnp.zeros((2, 4, 8, 3, 16)), jnp.zeros((2, 4, 8, 3, 16)))]
    sh = shd.kv_cache_shardings(mesh, cache, strategy)
    assert sh[0].k.spec == jax.sharding.PartitionSpec()


@pytest.mark.parametrize("case", ["hybrid-window", "dense", "paged-merged",
                                  "paged-one-head"])
def test_kv_cache_shardings_split_whole_heads(case):
    """The kv-head axis is found from what each leaf is: [.., kv, hd]
    leaves (dense rows, a hybrid model's window caches) split axis -2,
    the paged pool's merged [.., kv * hd] row splits along it, and one
    KV head cannot split at all."""
    from repro.core.paging import PagingConfig
    mesh = shd.tp_mesh(jax.devices()[:2])
    strategy = shd.strategy_for_mesh(mesh)
    name = "recurrentgemma-2b" if case == "hybrid-window" else "qwen1.5-0.5b"
    kv = 1 if case == "paged-one-head" else 2
    cfg = dataclasses.replace(reduced_cfg(name), num_kv_heads=kv)
    paging = PagingConfig(block_size=8, num_blocks=16) \
        if case.startswith("paged") else None
    cache = Model(cfg).init_cache(4, 64, abstract=True, paging=paging)
    sh = shd.kv_cache_shardings(mesh, cache, strategy, kv_heads=kv,
                                head_dim=cfg.resolved_head_dim)
    kvs = [c for c in (cache if isinstance(cache, list) else [cache])
           if hasattr(c, "k")]
    shs = [c for c in (sh if isinstance(sh, list) else [sh])
           if hasattr(c, "k")]
    assert kvs
    P = jax.sharding.PartitionSpec
    for c, s in zip(kvs, shs):
        want = {"hybrid-window": P(None, None, "model"),
                "dense": P(None, None, None, "model"),
                "paged-merged": P(None, None, None, "model"),
                "paged-one-head": P()}[case]
        assert s.k.spec == want and s.v.spec == want, (c.k.shape, s.k.spec)


def test_mesh_spec_validation():
    with pytest.raises(ValueError, match="tp"):
        MeshSpec(tp=0)
    with pytest.raises(ValueError, match="bucketed"):
        RuntimeSpec(arch=CFG, mesh=MeshSpec(tp=2),
                    scheduler=SchedulerSpec(policy="bucketed"))
    with pytest.raises(ValueError, match="EngineCluster"):
        ServingEngine(_spec(mesh=MeshSpec(tp=1, dp=2)))


def test_submit_rejects_out_of_vocab_prompt(params):
    # an OOB embedding gather clamps differently on a sharded table than
    # an unsharded one — the engine must reject instead of diverging
    eng = ServingEngine(_spec())
    eng.load(params)
    with pytest.raises(ValueError, match="vocab"):
        eng.submit([CFG.vocab_size], max_new_tokens=2)


def test_tuner_explores_meshes_and_pins_single_device():
    from repro.harness.tune import DeviceProfile, WorkloadProfile, tune
    wl = WorkloadProfile(mean_prompt_len=16, max_prompt_len=32, burst_size=16)
    r1 = tune(CFG, DeviceProfile(cache_budget_bytes=1 << 20), wl)
    assert {(c.spec.mesh.tp, c.spec.mesh.dp) for c in r1.ranked} == {(1, 1)}
    r4 = tune(CFG, DeviceProfile(cache_budget_bytes=1 << 20, n_devices=4),
              wl)
    assert {(c.spec.mesh.tp, c.spec.mesh.dp) for c in r4.ranked} \
        == {(1, 4), (2, 2), (4, 1)}
    # fleet capacity scales with dp: the 4-device winner must beat the
    # 1-device winner on predicted goodput
    assert r4.best.score > r1.best.score


def test_analytical_tp_term_monotone():
    from repro.configs.base import ShapeSpec
    from repro.core.analytical import analytical_step_seconds
    shape = ShapeSpec("t", 128, 4, "decode")
    base = analytical_step_seconds(CFG, shape, 1)
    same = analytical_step_seconds(CFG, shape, 1, tp=1)
    assert base.bytes_collective == same.bytes_collective  # pinned
    prev = 0.0
    for tp in (2, 4, 8):
        terms = analytical_step_seconds(CFG, shape, tp, tp=tp)
        assert terms.bytes_collective > prev
        prev = terms.bytes_collective
