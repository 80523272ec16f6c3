"""Paged KV-cache subsystem: allocator, kernel, admission, preemption."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_cfg
from repro.core.paging import (NULL_BLOCK, BlockAllocator, PagingConfig,
                               blocks_for_tokens)
from repro.kernels.paged_attention import paged_decode_attention
from repro.models.model import Model, ModelOptions
from repro.serving.engine import ServingEngine
from repro.serving.sampling import SamplingParams, sample_per_slot


# ---------------------------------------------------------------------------
# BlockAllocator
# ---------------------------------------------------------------------------
def test_allocator_alloc_free_roundtrip():
    a = BlockAllocator(PagingConfig(block_size=16, num_blocks=8))
    assert a.num_free == 8
    got = a.alloc(3)
    assert len(got) == 3 and a.num_free == 5
    assert NULL_BLOCK not in got            # block 0 is never handed out
    assert len(set(got)) == 3
    a.free(got)
    assert a.num_free == 8


def test_allocator_oom_returns_none_without_side_effects():
    a = BlockAllocator(PagingConfig(block_size=16, num_blocks=4))
    first = a.alloc(3)
    assert a.alloc(2) is None
    assert a.num_free == 1                  # failed alloc took nothing
    a.free(first)
    assert a.alloc(4) is not None


def test_allocator_lifo_reuse_and_double_free():
    a = BlockAllocator(PagingConfig(block_size=16, num_blocks=4))
    got = a.alloc(2)
    a.free(got)
    assert a.alloc(1)[0] == got[0]          # just-freed block comes back first
    with pytest.raises(ValueError, match="double free"):
        a.free([a.alloc(1)[0]] * 2)


def test_fragmentation_stats():
    a = BlockAllocator(PagingConfig(block_size=16, num_blocks=8))
    a.alloc(4)
    a.set_used_tokens(40)                   # 40 of 4*16=64 token capacity
    s = a.stats()
    assert s.used_blocks == 4 and s.free_blocks == 4
    assert s.utilization == pytest.approx(0.5)
    assert s.internal_fragmentation == pytest.approx(1 - 40 / 64)


def test_blocks_for_tokens():
    assert blocks_for_tokens(0, 16) == 0
    assert blocks_for_tokens(1, 16) == 1
    assert blocks_for_tokens(16, 16) == 1
    assert blocks_for_tokens(17, 16) == 2


# ---------------------------------------------------------------------------
# Pallas paged-decode kernel (interpret mode) vs the dense contraction
# ---------------------------------------------------------------------------
def _reference(q, k_pool, v_pool, tables, lengths):
    B, h, hd = q.shape
    kv = k_pool.shape[2]
    T = tables.shape[1] * k_pool.shape[1]
    kg = k_pool[tables].reshape(B, T, kv, hd)
    vg = v_pool[tables].reshape(B, T, kv, hd)
    kf = jnp.repeat(kg, h // kv, axis=2)    # repeat_kv's head ordering
    vf = jnp.repeat(vg, h // kv, axis=2)
    s = jnp.einsum("bhd,bkhd->bhk", q, kf) / math.sqrt(hd)
    live = (jnp.arange(T)[None] < lengths[:, None])[:, None]
    s = jnp.where(live, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", p, vf)


@pytest.mark.parametrize("h,kv", [(4, 4), (8, 2)])
def test_paged_kernel_matches_dense_path(h, kv):
    rng = np.random.RandomState(0)
    B, hd, bs, nblk = 3, 16, 8, 4
    NB = 1 + B * nblk
    q = jnp.asarray(rng.randn(B, h, hd), jnp.float32)
    kp = jnp.asarray(rng.randn(NB, bs, kv, hd), jnp.float32)
    vp = jnp.asarray(rng.randn(NB, bs, kv, hd), jnp.float32)
    # scattered, non-contiguous physical blocks
    tables = jnp.asarray(
        rng.permutation(np.arange(1, NB)).reshape(B, nblk), jnp.int32)
    lengths = jnp.asarray([5, 17, nblk * bs], jnp.int32)
    out = paged_decode_attention(q, kp, vp, tables, lengths, interpret=True)
    ref = _reference(q, kp, vp, tables, lengths)
    assert jnp.allclose(out, ref, atol=1e-5)


def test_paged_kernel_ignores_null_block_entries():
    """Table entries past the allocated blocks point at the null block;
    masked columns must contribute exactly zero even if block 0 holds
    garbage."""
    rng = np.random.RandomState(1)
    B, h, kv, hd, bs, nblk = 1, 4, 2, 16, 8, 4
    NB = 1 + nblk
    q = jnp.asarray(rng.randn(B, h, hd), jnp.float32)
    kp = jnp.asarray(rng.randn(NB, bs, kv, hd), jnp.float32)
    vp = jnp.asarray(rng.randn(NB, bs, kv, hd), jnp.float32)
    kp = kp.at[NULL_BLOCK].set(1e4)         # poison the null block
    vp = vp.at[NULL_BLOCK].set(1e4)
    tables = jnp.asarray([[1, 2, NULL_BLOCK, NULL_BLOCK]], jnp.int32)
    lengths = jnp.asarray([11], jnp.int32)
    out = paged_decode_attention(q, kp, vp, tables, lengths, interpret=True)
    ref = _reference(q, kp, vp, tables, lengths)
    assert jnp.allclose(out, ref, atol=1e-5)
    assert bool(jnp.all(jnp.abs(out) < 1e3))


@pytest.mark.parametrize("h,kv", [(4, 4), (8, 2)])
def test_paged_kernel_int8_pool_matches_dequantized_reference(h, kv):
    """int8 pool + per-(entry, kv-head) scales: the kernel's fused dequant
    (K scale on the score columns, V scale on the probabilities) equals
    attention over the dequantized pool; a poisoned null block, scales
    included, contributes nothing."""
    from repro.core.kv_quant import CacheCodec
    codec = CacheCodec("int8")
    rng = np.random.RandomState(2)
    B, hd, bs, nblk = 3, 16, 8, 4
    NB = 1 + B * nblk
    q = jnp.asarray(rng.randn(B, h, hd), jnp.float32)
    # rows of very different magnitude, so a dropped or misrouted scale
    # shows up far above the tolerance
    mag = jnp.asarray(np.exp(rng.randn(NB, bs, kv, 1)), jnp.float32)
    kq, ks = codec.encode(jnp.asarray(rng.randn(NB, bs, kv, hd)) * mag)
    vq, vs = codec.encode(jnp.asarray(rng.randn(NB, bs, kv, hd)) * mag)
    kq, vq = kq.at[NULL_BLOCK].set(127), vq.at[NULL_BLOCK].set(127)
    ks, vs = ks.at[NULL_BLOCK].set(1e4), vs.at[NULL_BLOCK].set(1e4)
    perm = rng.permutation(np.arange(1, NB)).reshape(B, nblk)
    perm[0, 2:] = NULL_BLOCK                # slot 0 holds two blocks
    tables = jnp.asarray(perm, jnp.int32)
    lengths = jnp.asarray([11, 17, nblk * bs], jnp.int32)
    out = paged_decode_attention(q, kq, vq, tables, lengths,
                                 k_scale=ks, v_scale=vs, interpret=True)
    ref = _reference(q, codec.decode(kq, ks, jnp.float32),
                     codec.decode(vq, vs, jnp.float32), tables, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Model-level cache-layout interface
# ---------------------------------------------------------------------------
def test_init_cache_pool_shapes():
    cfg = reduced_cfg("qwen1.5-0.5b")
    model = Model(cfg)
    paging = PagingConfig(block_size=8, num_blocks=12)
    cache = model.init_cache(4, 64, abstract=True, paging=paging)
    # +1 null block row; heads narrower than 128 lanes share one row
    assert cache.k.shape == (cfg.num_layers, 13, 8,
                             cfg.num_kv_heads * cfg.resolved_head_dim)
    wide = Model(dataclasses.replace(cfg, head_dim=128))
    cache = wide.init_cache(4, 64, abstract=True, paging=paging)
    assert cache.k.shape == (cfg.num_layers, 13, 8, cfg.num_kv_heads, 128)


def test_init_cache_paged_rejects_ssm():
    cfg = reduced_cfg("falcon-mamba-7b")
    with pytest.raises(ValueError, match="unsupported for family"):
        Model(cfg).init_cache(2, 64, paging=PagingConfig(8, 8))


def test_engine_rejects_paged_for_hybrid():
    cfg = reduced_cfg("recurrentgemma-2b")
    with pytest.raises(ValueError, match="unsupported for family"):
        ServingEngine(Model(cfg), max_batch=2, max_len=64,
                      cache_layout="paged")


def test_engine_rejects_misaligned_block_size():
    cfg = reduced_cfg("qwen1.5-0.5b")
    with pytest.raises(ValueError, match="must divide"):
        ServingEngine(Model(cfg), max_batch=2, max_len=64,
                      cache_layout="paged", block_size=24)


# ---------------------------------------------------------------------------
# Engine: block-budget admission, preemption, decode off-by-one
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def qwen():
    cfg = reduced_cfg("qwen1.5-0.5b")
    model = Model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _run(model, params, reqs, **engine_kw):
    eng = ServingEngine(model, sampling=SamplingParams(), **engine_kw)
    eng.load(params)
    uids = [eng.submit(*r) for r in reqs]
    done = {r.uid: r for r in eng.run_to_completion()}
    return eng, [done[u] for u in uids]


def test_preemption_resumes_bit_identical(qwen):
    """A pool that cannot sustain two full requests must preempt the
    younger one and still produce both greedy streams unchanged.  The
    pool holds exactly one max_len request (the legal minimum), so two
    in-flight requests always collide."""
    model, params = qwen
    reqs = [(list(range(1, 9)), 20), (list(range(9, 17)), 20)]
    _, ref = _run(model, params, reqs, max_batch=2, max_len=32)
    eng, got = _run(model, params, reqs, max_batch=2, max_len=32,
                    cache_layout="paged", block_size=8, num_blocks=4)
    assert eng.stats["preemptions"] > 0
    assert [r.generated for r in got] == [r.generated for r in ref]


def test_pool_below_max_len_rejected_at_construction(qwen):
    """A pool that could never admit a full-length request used to fail
    mid-flight ('pool exhausted' RuntimeError) or strand prompts at
    submit; the spec now rejects the geometry at construction, which
    makes both of those late failure paths unreachable (any single
    request fits the pool, so preemption always makes progress)."""
    model, _ = qwen
    with pytest.raises(ValueError, match="never be admitted"):
        ServingEngine(model, max_batch=2, max_len=64,
                      sampling=SamplingParams(), cache_layout="paged",
                      block_size=8, num_blocks=4)    # 32 tokens < 64
    from repro.core.spec import MemorySpec
    with pytest.raises(ValueError, match="num_blocks >= 8"):
        MemorySpec(cache_layout="paged", max_len=64, block_size=8,
                   num_blocks=1)


def test_decode_uses_final_cache_position(qwen):
    """Regression for the decode off-by-one: with an unbounded budget a
    prompt of length P must yield max_len - P + 1 tokens (the prefill
    sample plus one per remaining cache position, *including* position
    max_len - 1), in both layouts."""
    model, params = qwen
    for kw in ({}, {"cache_layout": "paged", "block_size": 8}):
        eng, (req,) = _run(model, params, [([1, 2, 3], 100)],
                           max_batch=2, max_len=32, **kw)
        assert len(req.generated) == 32 - 3 + 1, kw


def test_max_len_prompt_with_budget_one(qwen):
    """A max_len-length prompt is admissible when its single token comes
    from the prefill sample (the aligned submit guard)."""
    model, params = qwen
    eng, (req,) = _run(model, params, [(list(range(1, 33)), 1)],
                       max_batch=2, max_len=32)
    assert len(req.generated) == 1
    with pytest.raises(ValueError, match="max_new_tokens must be 1"):
        eng.submit(list(range(1, 33)), max_new_tokens=2)


def test_fragmentation_accounting(qwen):
    model, params = qwen
    eng = ServingEngine(model, max_batch=4, max_len=64,
                        sampling=SamplingParams(), cache_layout="paged",
                        block_size=16, num_blocks=16)
    eng.load(params)
    eng.submit([1, 2, 3], max_new_tokens=8)      # mid-flight after one step
    eng.step()
    s = eng.memory_stats()
    assert s.used_blocks >= 1
    assert 0.0 < s.internal_fragmentation < 1.0
    eng.run_to_completion()
    assert eng.memory_stats().used_blocks == 0   # harvest returned blocks


# ---------------------------------------------------------------------------
# Admission edges — all must stay on the single decode trace
# ---------------------------------------------------------------------------
def test_admission_edges_one_decode_trace(qwen):
    model, params = qwen
    eng = ServingEngine(model, max_batch=4, max_len=64,
                        sampling=SamplingParams(), cache_layout="paged",
                        block_size=8)
    eng.load(params)
    u_bucket = eng.submit(list(range(1, 33)), max_new_tokens=4)  # len == bucket 32
    u_budget1 = eng.submit([9, 8, 7], max_new_tokens=1)
    done = {r.uid: r for r in eng.run_to_completion()}
    assert len(done[u_bucket].generated) == 4
    assert len(done[u_budget1].generated) == 1
    # eos equal to the first prefill-sampled token must stop at one token
    first = done[u_budget1].generated[0]
    u_eos = eng.submit([9, 8, 7], max_new_tokens=50, eos_id=first)
    done2 = {r.uid: r for r in eng.run_to_completion()}
    assert done2[u_eos].generated == [first]
    assert eng.compilations["decode"] == 1


def test_per_request_sampling_no_retrace(qwen):
    """Mixing greedy / top-k / top-p requests in one batch must not add
    decode traces: the sampling knobs are device data, not constants."""
    model, params = qwen
    eng = ServingEngine(model, max_batch=4, max_len=64,
                        sampling=SamplingParams())
    eng.load(params)
    u_greedy = eng.submit([1, 2, 3], max_new_tokens=5)
    eng.submit([4, 5, 6], max_new_tokens=5,
               sampling=SamplingParams(temperature=0.8, top_k=3))
    eng.submit([7, 8, 9], max_new_tokens=5,
               sampling=SamplingParams(temperature=1.2, top_p=0.5))
    done = {r.uid: r for r in eng.run_to_completion()}
    assert all(len(r.generated) == 5 for r in done.values())
    assert eng.compilations["decode"] == 1
    # the greedy stream must equal a greedy-only run (row isolation)
    eng2 = ServingEngine(model, max_batch=4, max_len=64,
                         sampling=SamplingParams())
    eng2.load(params)
    u2 = eng2.submit([1, 2, 3], max_new_tokens=5)
    ref = {r.uid: r for r in eng2.run_to_completion()}
    assert done[u_greedy].generated == ref[u2].generated


def test_sample_per_slot_support():
    logits = jnp.asarray([[0.0, 1.0, 2.0, 30.0],
                          [10.0, 9.0, -10.0, -10.0],
                          [1.0, 5.0, 2.0, 0.0]])
    temp = jnp.asarray([1.0, 1.0, 0.0], jnp.float32)
    top_k = jnp.asarray([2, 0, 0], jnp.int32)
    top_p = jnp.asarray([1.0, 0.9, 1.0], jnp.float32)
    for i in range(20):
        t = sample_per_slot(logits, jax.random.PRNGKey(i), temp, top_k, top_p)
        assert int(t[0]) in (2, 3)          # top-k row
        assert int(t[1]) in (0, 1)          # top-p row
        assert int(t[2]) == 1               # greedy row == argmax


# ---------------------------------------------------------------------------
# MLA paged layout
# ---------------------------------------------------------------------------
def test_mla_paged_matches_dense():
    cfg = reduced_cfg("deepseek-v3-671b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    streams = {}
    for layout in ("dense", "paged"):
        eng = ServingEngine(model, max_batch=2, max_len=64,
                            sampling=SamplingParams(), cache_layout=layout,
                            block_size=8)
        eng.load(params)
        uid = eng.submit([5, 6, 7], max_new_tokens=5)
        done = eng.run_to_completion()
        streams[layout] = next(r for r in done if r.uid == uid).generated
    assert streams["dense"] == streams["paged"]


@pytest.mark.parametrize("name,kv_dtype,unroll", [
    ("deepseek-v3-671b", "int8", False),     # MoE dense prefix, MLA codec
    ("deepseek-v3-671b", "compute", True),
    ("qwen1.5-0.5b", "int8", False),
    ("qwen1.5-0.5b", "compute", True),
])
def test_paged_matches_dense_streams(name, kv_dtype, unroll):
    """The paged pool carried through the layer loop (written and read in
    place at each layer's index) serves the dense layout's greedy
    streams: with a MoE dense prefix, under the int8 codec, and on the
    unrolled layer loop."""
    from repro.core.spec import MemorySpec, RuntimeSpec
    cfg = reduced_cfg(name)
    params = Model(cfg).init(jax.random.PRNGKey(0))
    reqs = [([5, 6, 7], 6), (list(range(1, 20)), 5), ([9, 8], 7)]
    streams = {}
    for layout in ("dense", "paged"):
        if unroll:      # a Model instance keeps its build options
            eng = ServingEngine(Model(cfg, ModelOptions(unroll_layers=True)),
                                sampling=SamplingParams(), max_batch=2,
                                max_len=64, cache_layout=layout,
                                block_size=8)
        else:
            eng = ServingEngine(RuntimeSpec(arch=cfg, memory=MemorySpec(
                cache_layout=layout, max_batch=2, max_len=64, block_size=8,
                kv_dtype=kv_dtype)), sampling=SamplingParams())
        eng.load(params)
        assert eng.model.opt.unroll_layers == unroll
        assert eng.model.codec.kv_dtype == kv_dtype
        uids = [eng.submit(*r) for r in reqs]
        done = {r.uid: r.generated for r in eng.run_to_completion()}
        streams[layout] = [done[u] for u in uids]
    assert streams["dense"] == streams["paged"]


# ---------------------------------------------------------------------------
# Refcounted blocks + the prefix trie (PR 7)
# ---------------------------------------------------------------------------
def test_allocator_refcounts():
    from repro.core.paging import PagingConfig
    a = BlockAllocator(PagingConfig(block_size=8, num_blocks=8))
    got = a.alloc(2)
    assert [a.ref(b) for b in got] == [1, 1]
    a.incref(got)                       # a second request maps the blocks
    assert a.decref(got) == []          # first release: nothing hits zero
    assert a.num_free == 6              # ...so nothing was freed
    zeros = a.decref(got)
    assert zeros == got                 # second release: both at zero
    a.free(zeros)
    assert a.num_free == 8
    with pytest.raises(ValueError, match="unreferenced"):
        a.decref(got)                   # blocks are free again
    b = a.alloc(1)
    a.incref(b)
    with pytest.raises(ValueError, match="still mapped"):
        a.free(b)                       # refcount 2: free is an error
    with pytest.raises(ValueError, match="incref of free"):
        a.incref([a._free[0]])


def test_allocator_free_set_stays_consistent():
    """The persistent free-set must mirror the free list through any
    interleaving of alloc/free (the O(1) double-free check)."""
    from repro.core.paging import PagingConfig
    a = BlockAllocator(PagingConfig(block_size=8, num_blocks=16))
    x, y = a.alloc(5), a.alloc(3)
    a.free(x[:2])
    z = a.alloc(4)
    a.free(x[2:] + y + z)
    assert a._free_set == set(a._free)
    assert a.num_free == 16
    with pytest.raises(ValueError, match="double free"):
        a.free([a._free[0]])


def test_prefix_trie_roundtrip_and_partial_match():
    from repro.core.paging import PagingConfig, PrefixCache
    a = BlockAllocator(PagingConfig(block_size=4, num_blocks=16))
    pc = PrefixCache(a)
    toks = list(range(10, 22))                 # 12 tokens = 3 full blocks
    blocks = a.alloc(3)
    assert pc.insert(0, toks, blocks) == 3
    # full-prefix hit, capped below the last token
    hit = pc.lookup(0, toks + [99], limit=12)
    assert hit.blocks == blocks and hit.tokens == 12
    # divergence inside block 2 -> partial (CoW fork) match
    div = toks[:6] + [77, 78, 79, 80]
    hit = pc.lookup(0, div, limit=len(div) - 1)
    assert hit.blocks == blocks[:1] and hit.tokens == 4
    assert hit.fork_block == blocks[1] and hit.fork_tokens == 2
    # a different namespace shares nothing
    assert pc.lookup(1, toks, limit=12).cached_tokens == 0


def test_prefix_trie_park_evict_lru():
    from repro.core.paging import PagingConfig, PrefixCache
    a = BlockAllocator(PagingConfig(block_size=4, num_blocks=16))
    pc = PrefixCache(a)
    b1 = a.alloc(2)
    pc.insert(0, [1, 2, 3, 4, 5, 6, 7, 8], b1)
    b2 = a.alloc(1)
    pc.insert(0, [9, 9, 9, 9], b2)
    # release both chains: trie-owned blocks park instead of freeing
    assert pc.park(a.decref(b1 + b2)) == []
    assert a.num_free == 13 and pc.num_parked == 3
    assert a.stats().cached_blocks == 3
    # oldest chain evicts first, leaf before parent, never a live block
    hit = pc.lookup(0, [9, 9, 9, 9, 0], limit=4)
    pc.acquire(hit)                            # pin the younger chain
    freed = pc.evict(3)
    assert freed == 2 and a.num_free == 15     # b1's two blocks only
    assert pc.lookup(0, [1, 2, 3, 4], limit=4).cached_tokens == 0
    assert pc.lookup(0, [9, 9, 9, 9, 0], limit=4).tokens == 4
    pc.release(hit)


def test_prefix_trie_insert_existing_node_wins():
    """Registering a duplicate chain must keep the original block; the
    caller's copy stays private (freed at its own release)."""
    from repro.core.paging import PagingConfig, PrefixCache
    a = BlockAllocator(PagingConfig(block_size=4, num_blocks=8))
    pc = PrefixCache(a)
    b1 = a.alloc(1)
    assert pc.insert(0, [5, 6, 7, 8], b1) == 1
    b2 = a.alloc(1)
    assert pc.insert(0, [5, 6, 7, 8], b2) == 0
    assert pc.lookup(0, [5, 6, 7, 8, 0], limit=4).blocks == b1
    assert not pc.owns(b2[0])


def _prefix_engine(params, *, prefix=True, max_batch=4, max_len=64,
                   block_size=8, num_blocks=None, kv_dtype="compute"):
    from repro.core.spec import MemorySpec, RuntimeSpec, SchedulerSpec
    cfg = reduced_cfg("qwen1.5-0.5b")
    spec = RuntimeSpec(
        arch=cfg,
        memory=MemorySpec(cache_layout="paged", max_batch=max_batch,
                          max_len=max_len, block_size=block_size,
                          num_blocks=num_blocks, kv_dtype=kv_dtype,
                          prefix_cache=prefix),
        scheduler=SchedulerSpec(policy="chunked", chunk_size=block_size))
    eng = ServingEngine(spec, sampling=SamplingParams())
    eng.load(params)
    return eng


@pytest.mark.parametrize("kv_dtype", ["compute", "int8"])
def test_prefix_sharing_bit_identical_streams(qwen, kv_dtype):
    """Cache-hit requests (full-block hits and a CoW fork) must stream
    exactly what the sharing-off engine streams, in both cache codecs,
    on one decode compilation."""
    _, params = qwen
    shared = list(range(1, 25))                # 3 full 8-token blocks
    waves = [[(shared + [30], 4)],
             [(shared + [40, 41], 4),          # full-block hit
              (shared[:20] + [99, 98], 4),     # CoW fork mid-block 3
              ([70, 71], 4)]]                  # unrelated miss
    streams = {}
    for prefix in (False, True):
        eng = _prefix_engine(params, prefix=prefix, kv_dtype=kv_dtype)
        outs = []
        for wave in waves:
            uids = [eng.submit(p, max_new_tokens=b) for p, b in wave]
            done = {r.uid: r.generated for r in eng.run_to_completion()}
            outs += [done[u] for u in uids]       # submission order
        streams[prefix] = outs
        assert eng.compilations["decode"] == 1
        if prefix:
            assert eng.stats["prefix_hits"] == 2
            assert eng.stats["cow_forks"] == 1
            s = eng.memory_stats()
            assert s.cached_blocks == 3        # parked after the drain
    assert streams[True] == streams[False]


def test_prefix_sharing_shared_block_accounting(qwen):
    """Concurrent holders of one prefix: the pool charges the shared
    blocks once and FragmentationStats reports them as shared."""
    _, params = qwen
    eng = _prefix_engine(params, max_batch=4, max_len=64, block_size=8)
    shared = list(range(1, 17))                # 2 full blocks
    eng.submit(shared + [5], max_new_tokens=2)
    eng.run_to_completion()                    # register the chain
    eng.submit(shared + [6], max_new_tokens=30)
    eng.submit(shared + [7], max_new_tokens=30)
    eng.step()
    s = eng.memory_stats()
    assert s.shared_blocks == 2                # both map the 2-block chain
    assert eng.allocator.ref(eng._slot_blocks[0][0]) == 2
    # physical residency: 2 shared + one private tail block each
    assert s.used_blocks < sum(len(b) for b in eng._slot_blocks)
    eng.run_to_completion()
    assert eng.memory_stats().shared_blocks == 0


def test_prefix_mid_prefill_preemption_rehits_trie(qwen):
    """Satellite: preempting a request mid-prefill while it HOLDS shared
    blocks must decref (never double-free), and its re-admission must
    re-hit the trie and stream bit-identically."""
    _, params = qwen
    shared = list(range(1, 17))                # 2 full 8-token blocks
    # A fills block 3 exactly, so its FIRST decode token needs a fourth
    # block; B's 44-token uncached suffix keeps it prefilling for many
    # steps.  The pool (9 blocks) is dry by then, nothing is parked
    # (both chain blocks are mapped), so A's growth preempts B —
    # youngest — mid-prefill while B holds the shared chain.
    reqs = [(shared + list(range(40, 48)), 8),
            (shared + list(range(50, 94)), 4)]
    streams = {}
    for prefix in (False, True):
        eng = _prefix_engine(params, prefix=prefix, max_batch=2,
                             max_len=64, block_size=8, num_blocks=9)
        if prefix:
            eng.submit(shared + [9], max_new_tokens=2)
            eng.run_to_completion()            # warm: register the chain
        uids = [eng.submit(p, max_new_tokens=b) for p, b in reqs]
        done = {r.uid: r.generated for r in eng.run_to_completion()}
        streams[prefix] = [done[u] for u in uids]
        if prefix:
            assert eng.stats["preemptions"] >= 1
            # A, B, and B's re-admission all hit the registered chain
            assert eng.stats["prefix_hits"] >= 3
            assert eng.memory_stats().used_blocks == eng.memory_stats() \
                .cached_blocks   # drained: only parked blocks resident
    assert streams[True] == streams[False]


# ---------------------------------------------------------------------------
# Speculative rollback: decref-aware block-tail truncate (PR 10)
# ---------------------------------------------------------------------------
def test_allocator_truncate_decref_aware():
    a = BlockAllocator(PagingConfig(block_size=8, num_blocks=8))
    got = a.alloc(4)
    kept, zeros = a.truncate(got, 2)
    assert kept == got[:2] and zeros == got[2:]
    a.free(zeros)
    assert a.num_free == 6
    with pytest.raises(ValueError, match="cannot keep"):
        a.truncate(got[:2], -1)
    kept, zeros = a.truncate(got[:2], 5)       # keep >= len: no-op
    assert kept == got[:2] and zeros == []


def test_rollback_while_shared_parks_trie_blocks():
    """Regression: a speculative rollback that truncates a slot's block
    tail while another request (or the trie) still holds the blocks must
    decref — never free.  Trie-owned blocks whose refcount hits zero
    park (stay resident for future prefix hits); only unowned remainders
    reach the free list."""
    from repro.core.paging import PrefixCache
    a = BlockAllocator(PagingConfig(block_size=4, num_blocks=8))
    pc = PrefixCache(a)
    chain = a.alloc(2)                     # slot A's blocks, registered
    pc.insert(0, list(range(1, 9)), chain)
    a.incref(chain)                        # slot B maps the same chain
    # slot A rewinds past block 2: refcount 2 -> 1, the block stays
    # mapped for B and must not surface in the zero list
    kept, zeros = a.truncate(chain, 1)
    assert kept == chain[:1] and zeros == []
    assert a.ref(chain[1]) == 1
    # slot B rewinds too: refcount hits zero, but the trie owns the
    # block — it parks instead of freeing
    kept, zeros = a.truncate(chain, 1)
    assert zeros == chain[1:]
    assert pc.park(zeros) == []            # trie-owned: parked, not freed
    assert pc.num_parked == 1
    assert chain[1] not in a._free
    assert a.stats().cached_blocks == 1
    # the parked tail is still a live prefix hit for future requests
    hit = pc.lookup(0, list(range(1, 9)) + [0], limit=8)
    assert hit.tokens == 8 and hit.blocks == chain


def test_prefix_cache_requires_paged_layout():
    from repro.core.spec import MemorySpec
    with pytest.raises(ValueError, match="requires cache_layout='paged'"):
        MemorySpec(cache_layout="dense", prefix_cache=True)
