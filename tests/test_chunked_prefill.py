"""Chunked prefill fused into the decode step.

The acceptance bar for the token-budget scheduler:

* greedy streams bit-identical to the bucketed baseline — dense, paged
  and fleet modes — with prefill compilations == 1 and decode == 1 after
  a mixed-length workload (sampled streams draw the same distributions
  on a different rng schedule),
* the per-step prompt-token total never exceeds ``token_budget`` and a
  short request's first token never waits for a long prompt's prefill,
* a slot preempted mid-prompt re-enters through the chunk scheduler and
  its stream stays bit-identical to an unpreempted run,
* the fused step donates the cache and SlotState buffers (no copy),
* the chunked-prefill Pallas kernel matches the gather reference.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_cfg
from repro.core.spec import (ExecutionSpec, MemorySpec, RuntimeSpec,
                             SchedulerSpec, maxima_for)
from repro.models.model import Model
from repro.serving.engine import ServingEngine
from repro.serving.sampling import SamplingParams

CFG = reduced_cfg("qwen1.5-0.5b")
CFG_B = dataclasses.replace(
    CFG, name="fleet-member-b", num_layers=1, d_model=48,
    num_heads=3, num_kv_heads=3, d_ff=96, vocab_size=96)

PROMPTS = [[1, 2, 3], list(range(1, 9)), [4], list(range(2, 40, 3)),
           [7, 7, 7, 7, 7], list(range(1, 20))]


@pytest.fixture(scope="module")
def params():
    return Model(CFG).init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def params_b():
    return Model(CFG_B).init(jax.random.PRNGKey(1))


def _engine(params, policy="auto", cache_layout="dense", maxima=None,
            max_batch=4, max_len=64, execution=None, **sched_kw):
    spec = RuntimeSpec(
        arch=CFG, maxima=maxima,
        execution=execution or ExecutionSpec(),
        memory=MemorySpec(cache_layout=cache_layout, max_batch=max_batch,
                          max_len=max_len, block_size=8),
        scheduler=SchedulerSpec(policy=policy, **sched_kw))
    eng = ServingEngine(spec, sampling=SamplingParams(),
                        **({"max_models": 2} if maxima is not None else {}))
    eng.load(params)
    return eng


def _drain(eng, prompts=PROMPTS, max_new=5, **submit_kw):
    uids = {eng.submit(p, max_new_tokens=max_new, **submit_kw): tuple(p)
            for p in prompts}
    done = eng.run_to_completion()
    assert len(done) == len(prompts)
    return {uids[r.uid]: r.generated for r in done}


# ---------------------------------------------------------------------------
# The headline claim: O(1) compilations, streams == bucketed baseline
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cache_layout", ["dense", "paged"])
def test_chunked_matches_bucketed(params, cache_layout):
    bucketed = _drain(_engine(params, "bucketed", cache_layout))
    eng = _engine(params, "chunked", cache_layout)
    chunked = _drain(eng)
    assert chunked == bucketed
    comp = eng.compilations()
    assert comp["prefill"] == 1 and comp["decode"] == 1
    assert comp["prefill_buckets"] == 0


def test_steady_state_uses_one_lane_decode(params):
    """Once no slot carries prompt work, the engine must dispatch the
    W == 1 fused decode (no chunk-lane overhead), still one compilation
    per program and one dispatch per step."""
    eng = _engine(params, "chunked")
    eng.submit([1, 2, 3], max_new_tokens=6)
    done = eng.run_to_completion()
    assert len(done[0].generated) == 6
    assert eng._step._cache_size() == 1      # the mixed step
    assert eng._decode._cache_size() == 1    # the steady-state decode
    comp = eng.compilations()
    assert comp["prefill"] == 1 and comp["decode"] == 1


def test_auto_policy_defaults_to_chunked(params):
    eng = _engine(params, "auto")
    assert eng.scheduler == "chunked"
    _drain(eng, prompts=PROMPTS[:2])
    assert eng.compilations["prefill"] == 1


def test_fleet_chunked_matches_bucketed(params, params_b):
    maxima = maxima_for(CFG, CFG_B, seq_max=64)
    for cache_layout in ("dense", "paged"):
        streams = {}
        for policy in ("bucketed", "chunked"):
            eng = _engine(params, policy, cache_layout, maxima=maxima)
            b_id = eng.add_model(params_b, CFG_B)
            uids = {eng.submit(p, max_new_tokens=5): ("a", tuple(p))
                    for p in PROMPTS[:3]}
            uids.update({eng.submit(p, max_new_tokens=5, model=b_id):
                         ("b", tuple(p)) for p in ([4, 5], [6, 7, 8])})
            done = eng.run_to_completion()
            assert len(done) == 5
            streams[policy] = {uids[r.uid]: r.generated for r in done}
            if policy == "chunked":
                comp = eng.compilations()
                assert comp["decode"] == 1 and comp["prefill"] == 1
        assert streams["chunked"] == streams["bucketed"], cache_layout


# ---------------------------------------------------------------------------
# Token budget + head-of-line behavior
# ---------------------------------------------------------------------------
def test_token_budget_bounds_per_step_prefill_tokens(params):
    eng = _engine(params, "chunked", chunk_size=8, token_budget=8)
    bucketed = _drain(_engine(params, "bucketed"))
    assert _drain(eng) == bucketed          # throttling never changes math
    assert eng.stats["max_step_prefill_tokens"] <= 8


def test_short_request_not_blocked_by_long_prompt(params):
    """A short prompt submitted after a long one must finish its whole
    stream while the long prompt is still prefilling — head-of-line
    blocking is what the bucketed path could not avoid."""
    eng = _engine(params, "chunked", chunk_size=8, token_budget=16)
    long_uid = eng.submit(list(range(1, 49)), max_new_tokens=4)
    short_uid = eng.submit([9, 8, 7], max_new_tokens=3)
    finished = []
    for _ in range(200):
        finished += eng.step()
        if any(r.uid == short_uid for r in finished):
            break
    assert any(r.uid == short_uid for r in finished)
    long_slot = next(s for s, r in enumerate(eng.slot_req)
                     if r is not None and r.uid == long_uid)
    assert eng._pf[long_slot] < 48          # long prompt still mid-prefill
    done = finished + eng.run_to_completion()
    assert {r.uid for r in done} == {long_uid, short_uid}


# ---------------------------------------------------------------------------
# Preemption x chunked prefill
# ---------------------------------------------------------------------------
def _ab_workload(eng):
    ua = eng.submit(list(range(1, 8)), max_new_tokens=6)    # 7 tokens, grows
    ub = eng.submit(list(range(10, 34)), max_new_tokens=4)  # 24 tokens
    done = {r.uid: r.generated for r in eng.run_to_completion()}
    return done[ua], done[ub]


def _tight_engine(params, maxima=None):
    spec = RuntimeSpec(
        arch=CFG, maxima=maxima,
        memory=MemorySpec(cache_layout="paged", max_batch=2, max_len=32,
                          block_size=8, num_blocks=4),
        scheduler=SchedulerSpec(policy="chunked", chunk_size=8))
    eng = ServingEngine(spec, sampling=SamplingParams(),
                        **({"max_models": 2} if maxima is not None else {}))
    eng.load(params)
    return eng


def test_mid_prefill_preemption_paged_bit_identical(params):
    """A pool of exactly 4 blocks seats A (1 block) + B (3 blocks); A's
    decode growth runs the pool dry while B is still mid-prompt, so B is
    preempted *before it ever produced a token* and must re-enter through
    the chunk scheduler — with both streams unchanged."""
    ref = _ab_workload(_engine(params, "chunked", "paged", max_batch=2,
                               max_len=32, chunk_size=8))
    eng = _tight_engine(params)
    got = _ab_workload(eng)
    assert eng.stats["preemptions"] > 0
    assert got == ref


def test_mid_prefill_preemption_fleet(params, params_b):
    maxima = maxima_for(CFG, CFG_B, seq_max=32)
    ref_eng = _engine(params, "chunked", "paged", maxima=maxima,
                      max_batch=2, max_len=32, chunk_size=8)
    ref = _ab_workload(ref_eng)
    eng = _tight_engine(params, maxima=maxima)
    got = _ab_workload(eng)
    assert eng.stats["preemptions"] > 0
    assert got == ref


def test_mid_prefill_preemption_dense_forced(params):
    """Dense layout has no organic preemption trigger; force one mid-chunk
    and check the stream is unchanged (single-request runs, so greedy
    recompute-resume must be exact)."""
    clean = _engine(params, "chunked", chunk_size=8)
    uid = clean.submit(list(range(10, 34)), max_new_tokens=4)
    want = {r.uid: r.generated for r in clean.run_to_completion()}[uid]

    eng = _engine(params, "chunked", chunk_size=8)
    uid2 = eng.submit(list(range(10, 34)), max_new_tokens=4)
    eng.step()                                   # one 8-token chunk in
    slot = next(s for s, r in enumerate(eng.slot_req)
                if r is not None and r.uid == uid2)
    assert 0 < eng._pf[slot] < 24                # genuinely mid-prefill
    eng._preempt(slot)
    assert eng.stats["preemptions"] == 1
    done = {r.uid: r.generated for r in eng.run_to_completion()}
    assert done[uid2] == want


# ---------------------------------------------------------------------------
# Donation: the fused step updates cache + SlotState in place
# ---------------------------------------------------------------------------
def test_fused_step_donates_cache_and_state(params):
    eng = _engine(params, "chunked")
    eng.submit([1, 2, 3], max_new_tokens=10)
    eng.step()   # admission + compile
    eng.step()
    ptrs = (eng.cache.k.unsafe_buffer_pointer(),
            eng.cache.v.unsafe_buffer_pointer(),
            eng.state.buf.unsafe_buffer_pointer(),
            eng.state.prompt_buf.unsafe_buffer_pointer())
    eng.step()   # steady state: no admissions, pure fused step
    assert (eng.cache.k.unsafe_buffer_pointer(),
            eng.cache.v.unsafe_buffer_pointer(),
            eng.state.buf.unsafe_buffer_pointer(),
            eng.state.prompt_buf.unsafe_buffer_pointer()) == ptrs


# ---------------------------------------------------------------------------
# SchedulerSpec validation + fallback
# ---------------------------------------------------------------------------
def test_scheduler_spec_validation():
    with pytest.raises(ValueError, match="policy"):
        SchedulerSpec(policy="eager")
    with pytest.raises(ValueError, match="token_budget"):
        SchedulerSpec(chunk_size=32, token_budget=16)
    with pytest.raises(ValueError, match="chunk_size"):
        SchedulerSpec(chunk_size=0)
    # block-geometry validation: explicit chunked must be satisfiable
    with pytest.raises(ValueError, match="block-aligned"):
        RuntimeSpec(arch=CFG,
                    memory=MemorySpec(cache_layout="paged", max_len=64,
                                      block_size=16),
                    scheduler=SchedulerSpec(policy="chunked", chunk_size=8))
    with pytest.raises(ValueError, match="sequential prefill"):
        RuntimeSpec(arch=reduced_cfg("falcon-mamba-7b"),
                    scheduler=SchedulerSpec(policy="chunked"))


def test_auto_falls_back_for_unchunkable(params):
    # ssm family: sequential prefill state -> bucketed
    cfg = reduced_cfg("falcon-mamba-7b")
    model = Model(cfg)
    eng = ServingEngine(RuntimeSpec(
        arch=cfg, memory=MemorySpec(max_batch=2, max_len=32)),
        sampling=SamplingParams())
    assert eng.scheduler == "bucketed"
    eng.load(model.init(jax.random.PRNGKey(0)))
    uid = eng.submit([1, 2, 3], max_new_tokens=3)
    done = eng.run_to_completion()
    assert len(next(r for r in done if r.uid == uid).generated) == 3
    # chunk/block misalignment -> bucketed under auto
    eng2 = _engine(params, "auto", "paged", max_len=64, chunk_size=12)
    assert eng2.scheduler == "bucketed"


# ---------------------------------------------------------------------------
# MLA + the Pallas chunk kernel
# ---------------------------------------------------------------------------
def test_mla_chunked_dense_matches_paged():
    cfg = reduced_cfg("deepseek-v3-671b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    streams = {}
    for layout in ("dense", "paged"):
        spec = RuntimeSpec(arch=cfg,
                           memory=MemorySpec(cache_layout=layout,
                                             max_batch=2, max_len=64,
                                             block_size=8),
                           scheduler=SchedulerSpec(policy="chunked",
                                                   chunk_size=8))
        eng = ServingEngine(spec, sampling=SamplingParams())
        eng.load(params)
        uid = eng.submit(list(range(5, 25)), max_new_tokens=5)
        done = eng.run_to_completion()
        streams[layout] = next(r for r in done if r.uid == uid).generated
        assert eng.compilations["prefill"] == 1
    assert streams["dense"] == streams["paged"]


def test_pallas_chunked_paged_smoke(params):
    eng = _engine(params, "chunked", "paged",
                  execution=ExecutionSpec(paged_attn_impl="pallas"),
                  max_batch=2, chunk_size=8)
    out = _drain(eng, prompts=[[1, 2, 3], list(range(1, 14))], max_new=4)
    assert all(len(v) == 4 for v in out.values())
    assert all(0 <= t < CFG.vocab_size for v in out.values() for t in v)
    assert eng.compilations["decode"] == 1


def test_chunked_kernel_matches_gather_reference():
    from repro.kernels.chunked_prefill import chunked_prefill_attention
    B, W, h, kv, hd = 3, 4, 8, 2, 16
    bs, nblk, NB = 8, 8, 12
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, W, h, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (NB, bs, kv, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (NB, bs, kv, hd), jnp.float32)
    bt = jax.random.randint(ks[3], (B, nblk), 1, NB).astype(jnp.int32)
    start = jnp.asarray([5, 0, 20], jnp.int32)
    out = chunked_prefill_attention(q, kp, vp, bt, start, interpret=True)

    t_max = nblk * bs
    n_rep = h // kv
    kf = jnp.repeat(kp[bt].reshape(B, t_max, kv, hd), n_rep, axis=2)
    vf = jnp.repeat(vp[bt].reshape(B, t_max, kv, hd), n_rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kf) / np.sqrt(hd)
    qpos = start[:, None] + jnp.arange(W)[None]
    mask = jnp.arange(t_max)[None, None, :] <= qpos[:, :, None]
    s = jnp.where(mask[:, None], s, -1e30)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vf)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)
    # fleet head-lane masking: dead KV-head groups are exact zeros
    live = jnp.asarray([1, 2, 2], jnp.int32)
    out2 = chunked_prefill_attention(q, kp, vp, bt, start, live_kv=live,
                                     interpret=True)
    assert bool(jnp.all(out2[0, :, n_rep:] == 0.0))
    np.testing.assert_array_equal(np.asarray(out2[0, :, :n_rep]),
                                  np.asarray(out[0, :, :n_rep]))


def test_chunked_kernel_int8_pool_matches_dequantized_reference():
    """int8 pool + per-(entry, kv-head) scales: the kernel's fused dequant
    equals chunk attention over the dequantized pool, and null-block
    entries past the live span, poisoned scales included, contribute
    nothing."""
    from repro.core.kv_quant import CacheCodec
    from repro.core.paging import NULL_BLOCK
    from repro.kernels.chunked_prefill import chunked_prefill_attention
    codec = CacheCodec("int8")
    B, W, h, kv, hd = 3, 4, 8, 2, 16
    bs, nblk, NB = 8, 8, 12
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    q = jax.random.normal(ks[0], (B, W, h, hd), jnp.float32)
    # rows of very different magnitude, so a dropped or misrouted scale
    # shows up far above the tolerance
    mag = jnp.exp(jax.random.normal(ks[4], (NB, bs, kv, 1)))
    kq, k_s = codec.encode(jax.random.normal(ks[1], (NB, bs, kv, hd)) * mag)
    vq, v_s = codec.encode(jax.random.normal(ks[2], (NB, bs, kv, hd)) * mag)
    kq, vq = kq.at[NULL_BLOCK].set(127), vq.at[NULL_BLOCK].set(127)
    k_s, v_s = k_s.at[NULL_BLOCK].set(1e4), v_s.at[NULL_BLOCK].set(1e4)
    bt = jax.random.randint(ks[3], (B, nblk), 1, NB).astype(jnp.int32)
    bt = bt.at[1, 2:].set(NULL_BLOCK)       # slot 1 spans two blocks
    start = jnp.asarray([5, 9, 20], jnp.int32)
    out = chunked_prefill_attention(q, kq, vq, bt, start, k_scale=k_s,
                                    v_scale=v_s, interpret=True)

    t_max = nblk * bs
    n_rep = h // kv
    kd = codec.decode(kq, k_s, jnp.float32)
    vd = codec.decode(vq, v_s, jnp.float32)
    kf = jnp.repeat(kd[bt].reshape(B, t_max, kv, hd), n_rep, axis=2)
    vf = jnp.repeat(vd[bt].reshape(B, t_max, kv, hd), n_rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kf) / np.sqrt(hd)
    qpos = start[:, None] + jnp.arange(W)[None]
    mask = jnp.arange(t_max)[None, None, :] <= qpos[:, :, None]
    s = jnp.where(mask[:, None], s, -1e30)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vf)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Prefix cache x chunked prefill (PR 7)
# ---------------------------------------------------------------------------
def _pfx_engine(params, maxima=None, *, prefix=True, max_batch=4,
                max_len=64, num_blocks=None):
    spec = RuntimeSpec(
        arch=CFG, maxima=maxima,
        memory=MemorySpec(cache_layout="paged", max_batch=max_batch,
                          max_len=max_len, block_size=8,
                          num_blocks=num_blocks, prefix_cache=prefix),
        scheduler=SchedulerSpec(policy="chunked", chunk_size=8))
    eng = ServingEngine(spec, sampling=SamplingParams(),
                        **({"max_models": 2} if maxima is not None else {}))
    eng.load(params)
    return eng


def test_prefix_hit_charges_budget_only_for_uncached_suffix(params):
    """A 33-token prompt whose first 32 tokens are cached must prefill
    in ONE chunk step (1 remaining token), where a cold engine needs
    ceil(33/8) grants — and the resumed slot starts at the cached span."""
    shared = list(range(1, 33))                  # 4 full blocks
    eng = _pfx_engine(params)
    eng.submit(shared + [40], max_new_tokens=2)
    eng.run_to_completion()                      # warm + register
    uid = eng.submit(shared + [41], max_new_tokens=4)
    eng.step()
    slot = next(s for s, r in enumerate(eng.slot_req)
                if r is not None and r.uid == uid)
    assert eng._pf[slot] == 33                   # 32 cached + 1 granted
    assert eng.stats["prefix_hit_tokens"] >= 32
    done = eng.run_to_completion()
    assert [r.uid for r in done] == [uid]


def test_prefix_forced_preemption_while_holding_shared_blocks(params):
    """Force-preempt a request mid-prefill while its block table maps
    the registered chain: release must decref (not double-free), the
    chain must survive for the re-admission to re-hit, and the stream
    must match a never-preempted engine."""
    shared = list(range(1, 17))                  # 2 full blocks
    prompt = shared + list(range(40, 64))        # + 24 uncached tokens

    clean = _pfx_engine(params)
    clean.submit(shared + [9], max_new_tokens=2)
    clean.run_to_completion()
    uid = clean.submit(prompt, max_new_tokens=4)
    want = {r.uid: r.generated for r in clean.run_to_completion()}[uid]

    eng = _pfx_engine(params)
    eng.submit(shared + [9], max_new_tokens=2)
    eng.run_to_completion()
    uid2 = eng.submit(prompt, max_new_tokens=4)
    eng.step()                                   # resumes at pf=16, +8
    slot = next(s for s, r in enumerate(eng.slot_req)
                if r is not None and r.uid == uid2)
    assert 16 < eng._pf[slot] < len(prompt)      # genuinely mid-prefill
    assert eng.allocator.ref(eng._slot_blocks[slot][0]) == 1  # chain held
    hits_before = eng.stats["prefix_hits"]
    eng._preempt(slot)                           # decref path, no free
    assert eng.stats["preemptions"] == 1
    done = {r.uid: r.generated for r in eng.run_to_completion()}
    assert eng.stats["prefix_hits"] == hits_before + 1   # re-hit on re-admit
    assert done[uid2] == want


def test_prefix_fleet_namespaces_isolate_models(params, params_b):
    """Identical token ids under different models must NOT share blocks:
    the trie is namespaced per (fleet, model, arch).  Same-model repeats
    still hit."""
    maxima = maxima_for(CFG, CFG_B, seq_max=64)
    eng = _pfx_engine(params, maxima=maxima)
    mb = eng.add_model(params_b, CFG_B)
    shared = list(range(1, 17))
    eng.submit(shared + [7], max_new_tokens=2, model=0)
    eng.run_to_completion()                      # registers under model 0
    eng.submit(shared + [8], max_new_tokens=2, model=mb)
    eng.run_to_completion()
    assert eng.stats["prefix_hits"] == 0         # cross-model: no sharing
    eng.submit(shared + [8], max_new_tokens=2, model=0)
    eng.submit(shared + [9], max_new_tokens=2, model=mb)
    done = eng.run_to_completion()
    assert len(done) == 2
    assert eng.stats["prefix_hits"] == 2         # each namespace hits itself
    # streams must equal a fleet engine with sharing off
    ref = _pfx_engine(params, maxima=maxima, prefix=False)
    ref.add_model(params_b, CFG_B)
    for m in (0, mb):
        ua = eng.submit(shared + [5, 6], max_new_tokens=3, model=m)
        ub = ref.submit(shared + [5, 6], max_new_tokens=3, model=m)
        ga = {r.uid: r.generated for r in eng.run_to_completion()}[ua]
        gb = {r.uid: r.generated for r in ref.run_to_completion()}[ub]
        assert ga == gb
