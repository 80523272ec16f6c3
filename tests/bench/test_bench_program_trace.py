"""The program-trace reduction on a hand-built trace (nanoseconds)."""
import sys
from pathlib import Path

# the benchmark lives beside src/, outside the package path
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest

from bench import manifest
from bench.program_trace import (ATTN_CORE, KV_POOL, MATMUL, UNSCOPED,
                                 host_idle_ms, reduce_program, self_times)
from bench.stats import Record
from repro.serving.events import SCOPE_NAMES

DEV = "/device:TPU:0"
HOST = "/host:CPU"
MS = 1_000_000.0
NEW_READERS = ("kv_pool_ms", "attn_core_ms", "matmul_ms", "unscoped_ms",
               "host_idle_ms", "padded_lane_share")


def _span(name, s, e, **st):
    return (HOST, "python", name, s * MS, (e - s) * MS, st)


def _op(s, e, op_name, name="fusion"):
    return (DEV, "XLA Ops", name, s * MS, (e - s) * MS, {"tf_op": op_name})


def _events():
    top = "jit(_mixed_impl)/jit(main)"
    return [
        _span("bench.window", 0, 100),
        _span("bench.step", 0, 40),
        _span("engine.step", 1, 39, step="7"),
        _span("engine.admit", 1, 3),
        _span("engine.dispatch", 3, 6, program="mixed"),
        _span("engine.harvest", 6, 39),
        _span("engine.harvest.wait", 7, 30),
        _span("engine.harvest.fetch", 33, 36),
        _span("bench.sleep", 40, 70),
        # one mixed step: the layer scan's while holds its body's ops
        (DEV, "XLA Modules", "jit__mixed_impl(1)", 5 * MS, 20 * MS, {}),
        _op(5, 20, f"{top}/while", "while.4"),
        _op(6, 10, f"{top}/while/body/attn.kv_gather/gather"),
        _op(10, 14, f"{top}/while/body/attn.core/dot_general"),
        _op(15, 19, f"{top}/while/body/attn.core/attn.out/dot_general"),
        _op(20, 24, f"{top}/head/dot_general"),
        _op(24, 25, f"{top}/while", "copy.107"),
        # the harvest's gather: busy, but no step program
        (DEV, "XLA Modules", "jit_gather(2)", 30 * MS, 2 * MS, {}),
        _op(30, 32, "jit(gather)/gather"),
    ]


def test_self_time_of_a_nested_while():
    ops = [(5, 20), (6, 10), (10, 14), (15, 19), (20, 24)]
    assert self_times(ops) == [3, 4, 4, 4, 4]
    # a grandchild subtracts from its parent only
    assert self_times([(0, 10), (1, 9), (2, 4)]) == [2, 6, 2]


def test_scopes_partition_the_step_programs_self_time():
    pt = reduce_program(_events(), SCOPE_NAMES)
    assert pt.executions == {"_mixed_impl": 1}
    got = {sc: ns / MS for (_, sc), ns in pt.self_ns.items()}
    assert got == pytest.approx({"attn.kv_gather": 4, "attn.core": 4,
                                 "attn.out": 4, "head": 4, UNSCOPED: 4})
    # scopes plus unscoped add up to the program's ops, the gather's
    # op (another program) in none of them
    assert sum(got.values()) == pytest.approx(pt.module_ns["_mixed_impl"]
                                              / MS)
    assert pt.scope_ms(KV_POOL) == pytest.approx(4.0)
    assert pt.scope_ms(ATTN_CORE) == pytest.approx(4.0)
    assert pt.scope_ms(MATMUL) == pytest.approx(8.0)
    assert pt.scope_ms((UNSCOPED,)) == pytest.approx(4.0)


def test_idle_time_goes_to_the_innermost_span():
    pt = reduce_program(_events(), SCOPE_NAMES)
    idle = {k: v / MS for k, v in pt.idle_ns.items()}
    # busy [5, 25] and [30, 32] of the 100 ms window
    assert idle == pytest.approx({
        "bench.step": 2, "engine.admit": 2, "engine.dispatch": 2,
        "engine.harvest.wait": 5, "engine.harvest": 4,
        "engine.harvest.fetch": 3, "bench.sleep": 30, "host": 30})
    assert pt.steps == 1
    assert pt.host_ns["engine.harvest"] == pytest.approx(33 * MS)
    # admission, dispatch and the harvest outside its reads: 2 + 2 + 4
    assert host_idle_ms(pt) == pytest.approx(8.0)
    assert pt.gaps[0][0] in ("bench.sleep", "host")
    assert pt.gaps[0][1] == pytest.approx(0.068)   # 32 -> 100 ms


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(RuntimeError):
        reduce_program([e for e in _events() if e[2] != "bench.window"],
                       SCOPE_NAMES)


def _rec(events=()):
    return Record(events=list(events), due={}, prompt_len={}, t0=10.0,
                  t1=20.0, t_drained=20.0, loop="rate", max_batch=4,
                  config={})


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_return_none_without_a_trace(name):
    assert manifest.metric_reader(name)(_rec()) is None


def test_device_readers_read_the_program_trace():
    rec = _rec()
    rec.program_trace = reduce_program(_events(), SCOPE_NAMES)
    got = {n: manifest.metric_reader(n)(rec) for n in NEW_READERS[:5]}
    assert got == pytest.approx({"kv_pool_ms": 4.0, "attn_core_ms": 4.0,
                                 "matmul_ms": 8.0, "unscoped_ms": 4.0,
                                 "host_idle_ms": 8.0})


def test_padded_lane_share_over_the_window_mixed_dispatches():
    def d(t, program, lanes, live):
        return ("dispatch", -1, 0, t, {"program": program, "lanes": lanes,
                                       "live_lanes": live})
    rec = _rec([d(9.0, "mixed", 64, 64),       # before the window
                d(11.0, "mixed", 64, 20), d(12.0, "decode", 4, 4),
                d(13.0, "mixed", 64, 12)])
    share = manifest.metric_reader("padded_lane_share.rate")(rec)
    assert share == pytest.approx(100.0 * (1 - 32 / 128))
