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

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and the test workers all import
this file.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.chunked_prefill import chunked_prefill_attention
from repro.kernels.paged_attention import paged_decode_attention

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
