"""The paged KV pool rides the layer loop's carry and is written in place.

Each layer scatters its new rows into the layer-stacked pool at
``(layer, block, offset)`` and gathers its view at ``(layer,
block_tables)``, so the compiled step programs hold no per-layer pool
array and never copy, restack or re-broadcast the whole pool: the
donated pool aliases the output.  Checked on the compiled CPU programs
of the engine's mixed and decode steps (GQA and MLA with a MoE dense
prefix, bf16 and int8 pools), and under tensor parallelism, where the
pool must keep its kv-head sharding through the loop.

``convert`` and fusions of the stacked shape are not asserted on: the
CPU backend widens a bf16 pool to f32 around its scatters, the chip
does not.
"""
import jax
import jax.numpy as jnp
import pytest

from conftest import POOL_MOVES, hlo_instructions, reduced_cfg
from repro.core.spec import MemorySpec, MeshSpec, RuntimeSpec, SchedulerSpec
from repro.models.model import Model
from repro.serving.engine import ServingEngine
from repro.serving.sampling import SamplingParams


def _engine(name, kv_dtype, mesh=MeshSpec()):
    cfg = reduced_cfg(name)
    eng = ServingEngine(RuntimeSpec(
        arch=cfg, mesh=mesh,
        memory=MemorySpec(cache_layout="paged", max_batch=4, max_len=64,
                          block_size=8, kv_dtype=kv_dtype),
        scheduler=SchedulerSpec(policy="chunked")),
        sampling=SamplingParams())
    eng.load(Model(cfg).init(jax.random.PRNGKey(0)))
    return eng


def _compiled(eng, program):
    args = (eng.params, eng.cache, eng.state, eng.block_tables)
    if program == "mixed":
        lowered = eng._step.lower(*args, jnp.ones((eng.max_batch,),
                                                  jnp.int32))
    else:
        lowered = eng._decode.lower(*args)
    return lowered.compile().as_text()


@pytest.mark.parametrize("kv_dtype", ["compute", "int8"])
@pytest.mark.parametrize("program", ["mixed", "decode"])
@pytest.mark.parametrize("name", ["qwen1.5-0.5b", "deepseek-v3-671b"])
def test_step_programs_write_the_pool_in_place(name, program, kv_dtype):
    eng = _engine(name, kv_dtype)
    stacked = {tuple(a.shape) for a in jax.tree.leaves(eng.cache)}
    per_layer = {s[1:] for s in stacked}
    instrs = hlo_instructions(_compiled(eng, program))
    moved = [(s, op) for s, op in instrs if s in stacked and op in POOL_MOVES]
    assert not moved, moved
    sliced = [(s, op) for s, op in instrs if s in per_layer]
    assert not sliced, sliced
    # the pool is still written: one scatter per stacked leaf at least
    assert {s for s, op in instrs if op == "scatter"} >= stacked


def test_tp_pool_stays_sharded_through_the_loop():
    """Under tp=2 every device scatters into and gathers from its own
    half of the pool's kv-major rows; no collective moves the pool."""
    eng = _engine("qwen1.5-0.5b", "compute", MeshSpec(tp=2))
    pool = jax.tree.leaves(eng.cache)[0]
    half = pool.shape[:-1] + (pool.shape[-1] // 2,)
    for program in ("mixed", "decode"):
        instrs = hlo_instructions(_compiled(eng, program))
        assert (half, "scatter") in instrs
        moved = [(s, op) for s, op in instrs if s in (pool.shape, half)
                 and (op in POOL_MOVES or op.startswith(("all-", "collective")))]
        assert not moved, (program, moved)
