"""The latent-attention kernel (``kernels/mla_attention.py``) against the
einsum chain it replaced (``kernels/ref.mla_attend_ref``), in interpret
mode, and the serving step programs that run it.

Cases cover one lane and a chunk of lanes; slots at position 0, in the
middle of a key block, at a block edge and at the view's last position,
mixed in one batch; a view shorter than one key block (the reduced
configs' 64 positions), several blocks, and a view that is not a whole
number of blocks, with lanes past its end.
"""
import jax
import jax.numpy as jnp
import pytest

from conftest import reduced_cfg
from repro.core.spec import MemorySpec, RuntimeSpec, SchedulerSpec
from repro.kernels.mla_attention import KEY_BLOCK, latent_attention
from repro.kernels.ref import mla_attend_ref
from repro.models.model import Model
from repro.serving.engine import ServingEngine
from repro.serving.sampling import SamplingParams

HEADS, RANK, ROPE = 4, 16, 8
CHUNK = 8
SCALE = 0.3

CASES = {
    # name: (lanes, view positions, each slot's first lane position)
    "one-lane-short-view": (1, 64, [0, 37, 63]),
    "chunk-short-view": (CHUNK, 64, [0, 21, 56]),
    "one-lane-blocks": (1, 3 * KEY_BLOCK,
                        [0, KEY_BLOCK - 1, KEY_BLOCK, 3 * KEY_BLOCK - 1]),
    "chunk-blocks": (CHUNK, 3 * KEY_BLOCK,
                     [KEY_BLOCK - CHUNK, KEY_BLOCK - 3, 700,
                      3 * KEY_BLOCK - CHUNK]),
    # the last slot's lanes run past the view's end
    "chunk-partial-block": (CHUNK // 2, 2 * KEY_BLOCK + 76,
                            [0, KEY_BLOCK + 1, 2 * KEY_BLOCK + 74]),
}


def _inputs(lanes, t_max, starts, dtype):
    ks = jax.random.split(jax.random.PRNGKey(lanes * t_max), 4)
    b = len(starts)
    q_lat = jax.random.normal(ks[0], (b, lanes, HEADS, RANK), jnp.float32)
    q_rope = jax.random.normal(ks[1], (b, lanes, HEADS, ROPE), dtype)
    c_kv = jax.random.normal(ks[2], (b, t_max, RANK), dtype)
    k_rope = jax.random.normal(ks[3], (b, t_max, ROPE), dtype)
    return q_lat, q_rope, c_kv, k_rope, jnp.asarray(starts, jnp.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_the_einsum_chain(case, dtype):
    q_lat, q_rope, c_kv, k_rope, start = _inputs(*CASES[case], dtype)
    got = latent_attention(q_lat, q_rope, c_kv, k_rope, start, scale=SCALE,
                           interpret=True)
    # the kernel's MXU operands are the view's dtype, so the chain gets
    # the absorbed query in that dtype too
    want = mla_attend_ref(q_lat.astype(dtype), q_rope, c_kv, k_rope, start,
                          scale=SCALE)
    assert got.shape == want.shape and got.dtype == dtype
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    # f32: summation order only; bf16: the output and the probabilities
    # fed to the value matmul are each rounded once, so two roundings at
    # the output's scale
    eps = float(jnp.finfo(dtype).eps)
    tol = (1e-5 if dtype == jnp.float32 else 2 * eps) \
        * float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= tol


def _walk(jaxpr, scope=""):
    """(primitive, enclosing named scopes, out avals) of every equation,
    nested jaxprs included, each under the scopes of the equations that
    hold it."""
    for eqn in jaxpr.eqns:
        here = f"{scope}/{eqn.source_info.name_stack}"
        yield eqn.primitive.name, here, [v.aval for v in eqn.outvars]
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub, here)


@pytest.mark.parametrize("program", ["mixed", "decode"])
def test_step_attends_in_the_kernel_with_no_score_tensor(program):
    """The reduced DeepSeek-V3 step programs run latent attention in the
    kernel under ``attn.core`` and hold no f32 score tensor over the
    whole view (B x heads x lanes x positions)."""
    cfg = reduced_cfg("deepseek-v3-671b")
    b, t_max = 4, 96
    eng = ServingEngine(RuntimeSpec(
        arch=cfg, memory=MemorySpec(cache_layout="paged", max_batch=b,
                                    max_len=t_max, block_size=8),
        scheduler=SchedulerSpec(policy="chunked", chunk_size=CHUNK)),
        sampling=SamplingParams())
    eng.load(Model(cfg).init(jax.random.PRNGKey(0)))
    args = (eng.params, eng.cache, eng.state, eng.block_tables)
    if program == "mixed":
        jaxpr = jax.make_jaxpr(eng._mixed_impl)(
            *args, jnp.ones((b,), jnp.int32))
        lanes = CHUNK
    else:
        jaxpr = jax.make_jaxpr(eng._decode_impl)(*args)
        lanes = 1
    eqns = list(_walk(jaxpr.jaxpr))
    kernels = [scope for prim, scope, _ in eqns if prim == "pallas_call"]
    assert any("attn.core" in s for s in kernels), kernels
    scores = b * cfg.num_heads * lanes * t_max
    big = [(prim, a.shape) for prim, _, avals in eqns for a in avals
           if getattr(a, "dtype", None) == jnp.float32
           and t_max in a.shape and a.size == scores]
    assert not big, big


def test_dispatch_counts_the_key_blocks_read():
    """``keys`` is the occupied slots' whole view; ``live_keys`` the
    positions of the key blocks up to each slot's last lane: whole
    blocks, at least one a slot, and more once a prompt crosses into a
    second block."""
    from repro.serving.events import EventLog

    cfg = reduced_cfg("deepseek-v3-671b")
    t_max = 2 * KEY_BLOCK
    eng = ServingEngine(RuntimeSpec(
        arch=cfg, memory=MemorySpec(cache_layout="paged", max_batch=2,
                                    max_len=t_max, block_size=8),
        scheduler=SchedulerSpec(policy="chunked", chunk_size=64)),
        sampling=SamplingParams())
    eng.load(Model(cfg).init(jax.random.PRNGKey(0)))
    log = EventLog()
    eng.events.subscribe(log)
    eng.submit([1, 2, 3], max_new_tokens=2)
    eng.submit([i % 100 + 1 for i in range(KEY_BLOCK + 20)],
               max_new_tokens=2)
    eng.run_to_completion()
    disp = [e.data for e in log.events if e.kind == "dispatch"]
    assert disp
    for d in disp:
        assert d["keys"] == d["slots"] * t_max
        assert d["live_keys"] % KEY_BLOCK == 0
        assert d["slots"] * KEY_BLOCK <= d["live_keys"] <= d["keys"]
    assert disp[0]["live_keys"] == disp[0]["slots"] * KEY_BLOCK
    assert any(d["live_keys"] == d["keys"] for d in disp)
