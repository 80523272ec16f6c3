"""The expert layer's per-layer metrics: ``expert_ms`` and
``expert_roofline`` on hand-built records, and the program-trace
reduction over the op names of a compiled MoE step program."""
import sys
from pathlib import Path

# the benchmark lives beside src/, outside the package path
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import json

import jax
import jax.numpy as jnp
import pytest

from bench.manifest import family_module, metric_reader
from bench.program_trace import (ProgramTrace, hlo_op_names, reduce_program)
from bench.stats import Record
from repro.serving.events import SCOPE_NAMES

ROOT = Path(__file__).resolve().parents[2]
MS = 1_000_000.0
MOE_SCOPES = ("moe.route", "moe.dispatch", "moe.experts", "moe.combine",
              "moe.shared")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
CFG = json.loads((ROOT / "bench/configs/deepseek-v3-l7.json").read_text())
FAM = family_module("deepseek_v3")


def _trace(self_ms: dict, executions: dict) -> ProgramTrace:
    """self ms per execution -> a ProgramTrace holding the totals."""
    self_ns = {(p, sc): ms * executions[p] * MS
               for (p, sc), ms in self_ms.items()}
    return ProgramTrace((0.0, 1e9), executions, {}, self_ns, {}, 1, {}, [])


def _record(pt, events) -> Record:
    return Record(events=events, due={}, prompt_len={}, t0=0.0, t1=10.0,
                  t_drained=10.0, loop="batch", max_batch=16, config=CFG,
                  program_trace=pt, family=FAM, peaks=PEAKS)


def _dispatch(t, program):
    return ("dispatch", -1, 0, t, {"program": program})


def _experts(t, rows, hit, n=1):
    return ("experts", -1, 0, t, {"expert_rows": rows, "experts_hit": hit,
                                  "dispatches": n})


def test_expert_ms_is_the_mean_of_the_programs_moe_self_time():
    pt = _trace({("_mixed_impl", "moe.experts"): 6.0,
                 ("_mixed_impl", "moe.route"): 1.0,
                 ("_decode_impl", "moe.experts"): 3.0,
                 ("_decode_impl", "moe.shared"): 1.0,
                 ("_decode_impl", "attn.core"): 50.0},
                {"_mixed_impl": 2, "_decode_impl": 6})
    assert metric_reader("expert_ms.batch")(_record(pt, [])) == \
        pytest.approx((7.0 + 4.0) / 2)


def test_expert_ms_is_silent_without_moe_scopes():
    pt = _trace({("_decode_impl", "ffn"): 3.0}, {"_decode_impl": 4})
    assert metric_reader("expert_ms.batch")(_record(pt, [])) is None
    assert metric_reader("expert_ms.batch")(_record(None, [])) is None


def test_expert_roofline_reads_the_work_of_each_program():
    """Decode: 10 rows and 4 experts hit a step; mixed: 100 rows and 20
    hit, one of its harvests covering two dispatches.  The least time of
    the mean work over the mean moe.experts time."""
    events = [_dispatch(1, "decode"), _experts(1.1, 10, 4),
              _dispatch(2, "mixed"), _dispatch(2.5, "mixed"),
              _experts(2.6, 200, 40, n=2),
              _dispatch(3, "decode"), _experts(3.1, 10, 4),
              # outside the window: not read
              _dispatch(11, "mixed"), _experts(11.1, 999, 99)]
    pt = _trace({("_mixed_impl", "moe.experts"): 4.0,
                 ("_decode_impl", "moe.experts"): 2.0},
                {"_mixed_impl": 2, "_decode_impl": 2})
    got = metric_reader("expert_roofline.batch")(_record(pt, events))
    flops = (FAM.expert_flops(CFG, 100) + FAM.expert_flops(CFG, 10)) / 2
    nbytes = (FAM.expert_bytes(CFG, 100, 20)
              + FAM.expert_bytes(CFG, 10, 4)) / 2
    least = max(flops / PEAKS["bf16_flops"],
                nbytes / PEAKS["hbm_bytes_per_s"])
    assert got == pytest.approx(100 * least / 3e-3)
    # weights bind: 12 experts' 3 x 7168 x 2048 bf16 matrices a step
    assert nbytes == pytest.approx(12 * 3 * 7168 * 2048 * 2
                                   + 55 * 2 * 7168 * 2)
    assert 0 < got <= 100


def test_expert_roofline_is_silent_where_no_expert_op_ran():
    events = [_dispatch(1, "decode"), _experts(1.1, 10, 4)]
    pt = _trace({("_decode_impl", "ffn"): 3.0}, {"_decode_impl": 1})
    read = metric_reader("expert_roofline.batch")
    assert read(_record(pt, events)) is None
    # a program without experts events (the parent commit's)
    pt = _trace({("_decode_impl", "moe.experts"): 3.0}, {"_decode_impl": 1})
    assert read(_record(pt, [_dispatch(1, "decode")])) is None


def test_a_compiled_moe_step_reduces_to_the_moe_scopes():
    """The mixed step of a tiny DeepSeek-V3 model compiled on the CPU: a
    trace of one execution whose ops carry only their HLO names (as on
    the chip, named from the dump) gives self time to every moe.* scope,
    the expert matmul kernel's included."""
    from bench.reference import deepseek_v3  # noqa: F401 (importable)
    from test_bench_deepseek_v3 import tiny_config

    from repro.serving.engine import ServingEngine
    from repro.serving.sampling import SamplingParams

    cfg = tiny_config("bf16")
    spec = FAM.spec(cfg, None)
    eng = ServingEngine(spec, sampling=SamplingParams())
    params = jax.eval_shape(lambda: FAM.to_program(cfg, FAM.make(
        cfg, jax.random.PRNGKey(0), jnp.bfloat16)))
    cache = jax.eval_shape(lambda: eng.model.init_cache(
        eng.max_batch, eng.max_len, paging=eng.paging))
    text = eng._step.lower(params, cache, eng.state, eng.block_tables,
                           jnp.ones((eng.max_batch,), jnp.int32)
                           ).compile().as_text()
    names = hlo_op_names(text)
    moe_ops = [n for n, op in names.items() if "/moe." in op]
    dev = "/device:TPU:0"
    events = [("/host:CPU", "python", "bench.window", 0.0, 100 * MS, {}),
              (dev, "XLA Modules", "jit__mixed_impl(1)", 1 * MS, 90 * MS,
               {})]
    events += [(dev, "XLA Ops", n, (1 + i * 0.01) * MS, 0.01 * MS, {})
               for i, n in enumerate(moe_ops)]
    pt = reduce_program(events, SCOPE_NAMES, {"_mixed_impl": names})
    scopes = {sc for _, sc in pt.self_ns}
    assert set(MOE_SCOPES) <= scopes, scopes
    assert pt.no_op_name_ns == 0
