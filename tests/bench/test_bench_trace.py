"""The trace reduction on a hand-built trace (nanoseconds)."""
import sys
from pathlib import Path

# the benchmark lives beside src/, outside the package path
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest

from bench.trace import merge, reduce_events

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _events():
    ms = 1_000_000.0
    return [
        (HOST, "python", "bench.window", 0.0, 100 * ms),
        (HOST, "python", "bench.step", 0.0, 40 * ms),
        (HOST, "python", "bench.sleep", 40 * ms, 30 * ms),
        (HOST, "python", "bench.step", 70 * ms, 30 * ms),
        # two step programs and their ops; one op straddles the window end
        (DEV, "XLA Modules", "jit__mixed_impl(1)", 5 * ms, 20 * ms),
        (DEV, "XLA Modules", "jit__decode_impl(2)", 75 * ms, 10 * ms),
        (DEV, "XLA Modules", "jit__decode_impl(2)", 90 * ms, 10 * ms),
        (DEV, "XLA Ops", "fusion.1", 5 * ms, 12 * ms),
        (DEV, "XLA Ops", "fusion.2", 15 * ms, 10 * ms),   # overlaps fusion.1
        (DEV, "XLA Ops", "fusion.1", 75 * ms, 10 * ms),
        (DEV, "XLA Ops", "copy.3", 95 * ms, 10 * ms),     # ends past 100 ms
        (DEV, "XLA Ops", "fusion.1", 200 * ms, 5 * ms),   # outside
    ]


def test_merge_unions_overlaps():
    assert merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_busy_union_and_window():
    r = reduce_events(_events())
    assert r.window_s == pytest.approx(0.1)
    # busy: [5, 25] + [75, 85] + [95, 100] ms
    assert r.busy[DEV] == [(5e6, 25e6), (75e6, 85e6), (95e6, 100e6)]
    assert r.busy_s() == pytest.approx(0.035)


def test_device_time_per_program():
    r = reduce_events(_events())
    assert r.program("_mixed_impl") == (pytest.approx(0.020), 1)
    assert r.program("_decode_impl") == (pytest.approx(0.020), 2)
    assert r.program("_prefill_impl") == (0.0, 0)


def test_idle_share_leaves_out_sleeps():
    r = reduce_events(_events())
    occ = r.occupied()
    assert occ == [(0.0, 40e6), (70e6, 100e6)]
    # busy inside the occupied stretches: 20 + 15 ms of 70 ms
    assert r.busy_s(occ) == pytest.approx(0.035)


def test_idle_gaps_are_named_by_the_host_span():
    gaps = dict((round(s, 6), n) for n, s in reduce_events(_events())
                .idle_gaps())
    assert gaps[0.05] == "bench.sleep"     # 25 -> 75 ms, mostly asleep
    assert gaps[0.005] == "bench.step"     # 0 -> 5 ms
    assert sorted(gaps) == [0.005, 0.01, 0.05]


def test_top_ops_sum_over_the_window():
    ops = dict(reduce_events(_events()).top_ops())
    # self time: fusion.2 covers 15 -> 17 ms of the first fusion.1
    assert ops["fusion.1"] == pytest.approx(0.020)
    assert ops["fusion.2"] == pytest.approx(0.010)
    assert ops["copy.3"] == pytest.approx(0.005)


def test_top_ops_rank_a_loop_by_its_own_time():
    ms = 1_000_000.0
    events = [(HOST, "python", "bench.window", 0.0, 100 * ms),
              # the layer loop's while holds its body's ops
              (DEV, "XLA Ops", "while.4", 0.0, 30 * ms),
              (DEV, "XLA Ops", "fusion.7", 1 * ms, 12 * ms),
              (DEV, "XLA Ops", "fusion.7", 14 * ms, 12 * ms),
              (DEV, "XLA Ops", "reshape.5", 26 * ms, 2 * ms),
              (DEV, "XLA Ops", "sort.1", 40 * ms, 5 * ms)]
    top = reduce_events(events).top_ops()
    assert [n for n, _ in top] == ["fusion.7", "sort.1", "while.4",
                                   "reshape.5"]
    assert dict(top)["while.4"] == pytest.approx(0.004)


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(RuntimeError):
        reduce_events([e for e in _events() if e[2] != "bench.window"])
