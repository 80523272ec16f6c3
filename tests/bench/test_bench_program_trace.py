"""The program-trace reduction on a hand-built trace (nanoseconds)."""
import sys
from pathlib import Path

# the benchmark lives beside src/, outside the package path
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import os

import pytest

from bench import manifest, run
from bench.program_trace import (ATTN_CORE, KV_POOL, MATMUL, UNSCOPED,
                                 host_idle_ms, load_hlo, reduce_program,
                                 roofline_share, self_times)
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
    # inside engine.step: admission, dispatch, and the harvest with its
    # wait and fetch: 2 + 2 + 4 + 5 + 3
    assert host_idle_ms(pt) == pytest.approx(16.0)
    assert pt.gaps[0][0] in ("bench.sleep", "host")
    assert pt.gaps[0][1] == pytest.approx(0.068)   # 32 -> 100 ms


@pytest.mark.parametrize("shift", [-1.0, -0.5, 0.5])
def test_host_idle_ms_holds_when_the_device_clock_shifts(shift):
    """The profiler's host and device clocks can disagree by a fraction of
    a millisecond from run to run: the idle gap then moves between the
    harvest's wait and the dispatch, and the metric must not move."""
    base = reduce_program(_events(), SCOPE_NAMES)
    moved = reduce_program(
        [(p, ln, n, s + shift * MS if p == DEV else s, d, st)
         for p, ln, n, s, d, st in _events()], SCOPE_NAMES)
    assert moved.idle_ns["engine.dispatch"] \
        != pytest.approx(base.idle_ns["engine.dispatch"])
    assert host_idle_ms(moved) == pytest.approx(host_idle_ms(base))


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
                                 "host_idle_ms": 16.0})


def test_padded_lane_share_over_the_window_mixed_dispatches():
    def d(t, program, lanes, live):
        return ("dispatch", -1, 0, t, {"program": program, "lanes": lanes,
                                       "live_lanes": live})
    rec = _rec([d(9.0, "mixed", 64, 64),       # before the window
                d(11.0, "mixed", 64, 20), d(12.0, "decode", 4, 4),
                d(13.0, "mixed", 64, 12)])
    share = manifest.metric_reader("padded_lane_share.rate")(rec)
    assert share == pytest.approx(100.0 * (1 - 32 / 128))


def test_roofline_share_takes_the_binding_bound():
    pt = reduce_program(_events(), SCOPE_NAMES)
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    # attn.core: 4 ms an execution; 2e9 FLOPs need 2 ms, 1e6 bytes 1 ms
    assert roofline_share(pt, ATTN_CORE, 2e9, 1e6, peaks) \
        == pytest.approx(50.0)
    # bytes bind: 3e6 bytes need 3 ms
    assert roofline_share(pt, ATTN_CORE, 2e9, 3e6, peaks) \
        == pytest.approx(75.0)
    # no op under the scope, or no trace: nothing to read
    assert roofline_share(pt, ("attn.kv_write",), 2e9, 1e6, peaks) is None
    assert roofline_share(None, ATTN_CORE, 2e9, 1e6, peaks) is None


def test_ops_without_op_name_are_named_from_the_dumped_hlo(tmp_path):
    (tmp_path / "module_0007.jit__mixed_impl.after_optimizations.txt"
     ).write_text(
        '  %fusion.9 = bf16[4]{0} fusion(%p), metadata={op_name='
        '"jit(_mixed_impl)/while/body/attn.core/dot_general"}\n'
        '  ROOT copy.2 = bf16[4]{0} copy(%fusion.9), metadata={op_name='
        '"jit(_mixed_impl)/while/body/attn.kv_write/scatter"}\n')
    hlo = load_hlo(tmp_path)
    assert set(hlo["_mixed_impl"]) == {"fusion.9", "copy.2"}
    assert hlo["_decode_impl"] == {}
    events = [e for e in _events() if e[1] != "XLA Ops"] + [
        (DEV, "XLA Ops", "%fusion.9 = bf16[4] fusion(%p)", 6 * MS, 4 * MS,
         {}),
        (DEV, "XLA Ops", "copy.2", 10 * MS, 2 * MS, {"hlo_op": "copy.2"})]
    pt = reduce_program(events, SCOPE_NAMES, hlo)
    assert pt.scope_ms(ATTN_CORE) == pytest.approx(4.0)
    assert pt.scope_ms(KV_POOL) == pytest.approx(2.0)
    assert pt.no_op_name_ns == 0.0
    # without the dump the same ops fall to unscoped
    bare = reduce_program(events, SCOPE_NAMES)
    assert bare.scope_ms((UNSCOPED,)) == pytest.approx(6.0)


def test_a_traced_run_compiles_its_step_programs_itself(monkeypatch,
                                                        tmp_path):
    monkeypatch.setenv("XLA_FLAGS", "--xla_foo=1")
    shared, own = tmp_path / "jax", tmp_path / "traced" / "cell"
    shared.mkdir()
    for n in ("jit__mixed_impl-aa-cache", "jit__decode_impl-bb-cache",
              "jit_gather-cc-cache", "jit_gather-dd-cache"):
        (shared / n).write_text(n)
    view = run.compile_steps_here(own, shared)
    flags = os.environ["XLA_FLAGS"].split()
    assert flags[0] == "--xla_foo=1"
    assert f"--xla_dump_to={own / 'hlo'}" in flags
    assert "--xla_dump_hlo_module_re=.*(_mixed_impl|_decode_impl).*" in flags
    # the view holds every entry but the step programs'
    assert sorted(p.name for p in view.iterdir()) == [
        "jit_gather-cc-cache", "jit_gather-dd-cache"]
    assert (view / "jit_gather-cc-cache").read_text() == "jit_gather-cc-cache"
    # what the run compiles joins the shared cache; links and entries the
    # shared cache holds already stay as they are
    (view / "jit__mixed_impl-aa-cache").write_text("again")
    (view / "jit_new-ee-cache").write_text("new")
    run.adopt(view, shared)
    assert sorted(p.name for p in shared.iterdir()) == [
        "jit__decode_impl-bb-cache", "jit__mixed_impl-aa-cache",
        "jit_gather-cc-cache", "jit_gather-dd-cache", "jit_new-ee-cache"]
    assert (shared / "jit__mixed_impl-aa-cache").read_text() \
        == "jit__mixed_impl-aa-cache"
    assert not any(p.is_symlink() for p in shared.iterdir())


@pytest.mark.parametrize("trace", [False, True])
def test_only_a_traced_run_dumps_and_reads_a_view(monkeypatch, tmp_path,
                                                  trace):
    monkeypatch.setattr(run, "CACHE", tmp_path)
    monkeypatch.setattr(run, "SHARED", tmp_path / "jax")
    monkeypatch.setenv("XLA_FLAGS", "--xla_foo=1")
    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    used = []
    monkeypatch.setattr(run, "use_cache", used.append)

    def no_chip(n):
        raise SystemExit(2)
    monkeypatch.setattr(run, "find_chips", no_chip)
    cell = manifest.load_cell("qwen1.5-0.5b.chat.rate")
    with pytest.raises(SystemExit):
        run.serve(cell, 1, 1.0, trace)
    own = tmp_path / "traced" / cell.name
    assert used == [own / "jax" if trace else tmp_path / "jax"]
    assert ("--xla_dump_to" in os.environ["XLA_FLAGS"]) == trace
    assert not own.exists()


def test_each_step_program_counts_alike():
    """Scope ms per execution of each step program, averaged over the
    programs: the mix of executions a seed draws does not move it."""
    top = "jit(_decode_impl)/jit(main)"

    def decodes(k):
        return [e for i in range(k) for e in [
            (DEV, "XLA Modules", "jit__decode_impl(2)", (40 + 10 * i) * MS,
             5 * MS, {}),
            _op(40 + 10 * i, 42 + 10 * i,
                f"{top}/while/body/attn.core/dot_general")]]
    reads = []
    for k in (1, 3):
        pt = reduce_program(_events() + decodes(k), SCOPE_NAMES)
        assert pt.executions == {"_mixed_impl": 1, "_decode_impl": k}
        assert pt.scope_ms(ATTN_CORE, "_mixed_impl") == pytest.approx(4.0)
        assert pt.scope_ms(ATTN_CORE, "_decode_impl") == pytest.approx(2.0)
        reads.append(pt.scope_ms(ATTN_CORE))
    assert reads == pytest.approx([3.0, 3.0])
