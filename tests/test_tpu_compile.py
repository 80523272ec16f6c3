"""The serving kernels compile for a TPU v5e at qwen1.5-0.5b widths.

Interpret mode (what the rest of the suite runs on CPU) accepts block
shapes that the TPU compiler refuses, so these tests lower each Pallas
kernel of the serving path for a described ``v5e:2x2`` topology — no
chip attached, nothing executed — and check that Mosaic accepted it
(the compiled text carries a ``tpu_custom_call``).

Shapes follow the one-chip serving configuration: 8 slots, 16 query and
16 KV heads of width 64, 16-token blocks, a 129-entry block table and a
W = 16 lane chunk; the matmuls are the FFN and vocabulary projections
at 128 rows (8 slots x 16 lanes).

The MoE layer's grouped expert matmul compiles at DeepSeek-V3's widths
(d 7168, expert width 2048, 8 held experts), and so does its latent
attention (128 heads, rank 512, 16 slots x 8192 positions).  The model's decode and
mixed steps are compiled too, at two layers of
the benchmark's widths (qwen1.5-0.5b: 32 slots x 2048 positions;
CodeQwen1.5-7B's 4 KV heads x 128: 16 x 8192) with the pool donated,
bf16 and int8: the chip's layouts must let every layer write and gather
the stacked pool in place, with no copy of the pool or of one layer of
it.  The dense cache layout's steps must fit one chip at qwen1.5-0.5b's
full serving size.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and the test workers all import
this file.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import POOL_MOVES, hlo_instructions
from repro.configs import REGISTRY
from repro.core.paging import PagingConfig
from repro.kernels import ops
from repro.kernels.chunked_prefill import chunked_prefill_attention
from repro.kernels.paged_attention import paged_decode_attention
from repro.models.model import Model, ModelOptions

B, H, KV, HD, BS, NBLK, W = 8, 16, 16, 64, 16, 129, 16
NB = B * NBLK + 1        # every slot's blocks plus the null block


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # an executable compiled for a described chip cannot be read back
        # without one; keep it out of any persistent cache
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _pool_shapes(int8: bool):
    vals = jnp.int8 if int8 else jnp.bfloat16
    pool = [((NB, BS, KV, HD), vals), ((NB, BS, KV, HD), vals)]
    scales = [((NB, BS, KV), jnp.float32)] * 2 if int8 else []
    return pool, scales


def _with_scales(kernel, int8: bool):
    if int8:
        return lambda *a: kernel(*a[:5], k_scale=a[5], v_scale=a[6],
                                 interpret=False)
    return functools.partial(kernel, interpret=False)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode_attention_compiles(one_chip, kv_dtype):
    int8 = kv_dtype == "int8"
    pool, scales = _pool_shapes(int8)
    compiled = _compile(
        _with_scales(paged_decode_attention, int8), one_chip,
        ((B, H, HD), jnp.bfloat16), *pool,
        ((B, NBLK), jnp.int32), ((B,), jnp.int32), *scales)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_chunked_prefill_attention_compiles(one_chip, kv_dtype):
    int8 = kv_dtype == "int8"
    pool, scales = _pool_shapes(int8)
    compiled = _compile(
        _with_scales(chunked_prefill_attention, int8), one_chip,
        ((B, W, H, HD), jnp.bfloat16), *pool,
        ((B, NBLK), jnp.int32), ((B,), jnp.int32), *scales)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k,n", [(1024, 2816), (2816, 1024), (1024, 151_936)])
def test_tiled_matmul_compiles(one_chip, monkeypatch, k, n):
    # ops reads the interpret probe at trace time; this process has no
    # TPU, so steer it to the kernel the chip would run
    monkeypatch.setattr(ops, "interpret_default", lambda: False)
    compiled = _compile(ops.tiled_matmul, one_chip,
                        ((B * W, k), jnp.bfloat16), ((k, n), jnp.bfloat16))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [16 * 8, 16 * 16 * 8])
@pytest.mark.parametrize("k,n", [(7168, 2048), (2048, 7168)])
def test_grouped_matmul_compiles(one_chip, rows, k, n):
    """The expert matmul at DeepSeek-V3's widths over the 8 experts one
    chip holds: a decode step's 16 slots x 8 picks and a mixed step's 16
    x 16 lanes x 8 rows, sorted by expert."""
    from repro.kernels.grouped_matmul import grouped_matmul

    compiled = _compile(
        functools.partial(grouped_matmul, interpret=False), one_chip,
        ((rows, k), jnp.bfloat16), ((8, k, n), jnp.bfloat16),
        ((8,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("lanes", [W, 1])
def test_mla_attention_compiles(one_chip, lanes):
    """Latent attention at DeepSeek-V3's widths (128 heads, rank 512,
    rope 64) over the cell's 16 slots x 8192 positions: a mixed step's
    16 lanes and a decode step's one."""
    from repro.kernels.mla_attention import latent_attention

    slots, positions, heads, rank, rope = 16, 8192, 128, 512, 64
    compiled = _compile(
        functools.partial(latent_attention, scale=0.135, interpret=False),
        one_chip, ((slots, lanes, heads, rank), jnp.float32),
        ((slots, lanes, heads, rope), jnp.bfloat16),
        ((slots, positions, rank), jnp.bfloat16),
        ((slots, positions, rope), jnp.bfloat16), ((slots,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


# (config, KV heads, slots, positions per slot) of the benchmark's cells
STEP_CELLS = {"qwen1.5-0.5b": ("qwen1.5-0.5b", 16, 32, 2048),
              "codeqwen1.5-7b": ("codeqwen1.5-7b", 4, 16, 8192)}


def _compile_step(one_chip, cell, program, layers, kv_dtype="compute",
                  paged=True):
    """(compiled step, its abstract cache) of ``Model.mixed_step`` or
    ``decode_step`` at a cell's widths, with the cache donated."""
    name, kv, slots, positions = STEP_CELLS[cell]
    cfg = dataclasses.replace(REGISTRY[name], num_layers=layers,
                              num_kv_heads=kv)
    model = Model(cfg, ModelOptions(param_dtype=jnp.bfloat16,
                                    kv_dtype=kv_dtype))
    paging = PagingConfig(block_size=BS, num_blocks=slots * positions // BS) \
        if paged else None
    on_chip = functools.partial(jax.tree.map, lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip))
    params = on_chip(model.abstract())
    cache = on_chip(model.init_cache(slots, positions, abstract=True,
                                     paging=paging))
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    tables = i32((slots, positions // BS)) if paged else None
    if program == "mixed":
        step = jax.jit(model.mixed_step, donate_argnums=(1,))
        lowered = step.lower(params, cache, i32((slots, W)), i32((slots,)),
                             i32((slots,)), tables)
    else:
        step = jax.jit(model.decode_step, donate_argnums=(1,))
        lowered = step.lower(params, cache, i32((slots, 1)), i32((slots,)),
                             tables)
    return lowered.compile(), cache


@pytest.mark.parametrize("kv_dtype", ["compute", "int8"])
@pytest.mark.parametrize("program", ["mixed", "decode"])
@pytest.mark.parametrize("cell", sorted(STEP_CELLS))
def test_model_steps_keep_the_pool_in_place(one_chip, cell, program,
                                            kv_dtype):
    compiled, cache = _compile_step(one_chip, cell, program, 2, kv_dtype)
    instrs = hlo_instructions(compiled.as_text())
    _, kv, slots, positions = STEP_CELLS[cell]
    hd = REGISTRY[STEP_CELLS[cell][0]].resolved_head_dim
    pool_5d = (slots * positions // BS + 1, BS, kv, hd)
    values = {tuple(cache.k.shape), tuple(cache.v.shape)}
    # int8 scale rows ([.., kv] f32, a narrow minor dim) may still be
    # copied whole between layouts; they are never sliced per layer
    scales = {tuple(a.shape) for a in (cache.k_scale, cache.v_scale)
              if a is not None}
    moved = [(s, op) for s, op in instrs
             if (s in values or s[1:] == pool_5d) and op in POOL_MOVES]
    assert not moved, moved
    per_layer = [(s, op) for s, op in instrs
                 if s in {p[1:] for p in values | scales} or s == pool_5d]
    assert not per_layer, per_layer


@pytest.mark.parametrize("program", ["mixed", "decode"])
def test_dense_cache_step_fits_one_chip(one_chip, program):
    """The dense [L, B, S] cache keeps the per-layer scan slices: at
    qwen1.5-0.5b's 24 layers and 32 x 2048 serving cache the step fits
    one v5e's HBM (carried whole, a [.., 16, 64] cache is copied in and
    out of the loop at 2x padding, 18.9 of 15.75 GiB)."""
    compiled, _ = _compile_step(one_chip, "qwen1.5-0.5b", program, 24,
                                paged=False)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * 2**30
