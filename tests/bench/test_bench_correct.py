"""``correct`` at a size a test run holds: a sound run passes, the
precision control and each planted fault of the timed path fail.

The cell is a two-layer Qwen2-style model on the CPU, driven through
``run.run_cell`` past the chip check: the same engine, window loop,
sampling and reference comparison as a chip run.
"""
import sys
from pathlib import Path

# the benchmark lives beside src/, outside the package path
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import json
import os

import jax.numpy as jnp
import pytest

from bench import run
from bench.manifest import Cell, load_cell
from repro.models.model import Model
from repro.serving import engine as engine_mod
from repro.serving.engine import ServingEngine

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 99
TINY = dict(hidden_size=256, intermediate_size=512, num_attention_heads=4,
            num_key_value_heads=2, head_dim=64, num_hidden_layers=2,
            vocab_size=4096)
# Over seeds 2**31 + 90..99 on the CPU a sound tiny run reads a mean gap
# of 0-0.00026 and the int8 control 0.00032-0.0020; on SEED 0 and
# 0.00037.  The limit sits between, as a chip cell's does.
TINY_LIMITS = {"mean_logit_gap": 0.0002}


def _cell() -> Cell:
    base = load_cell("qwen1.5-0.5b.chat.rate")
    cfg = dict(base.config, **TINY)
    cfg["serving"] = dict(cfg["serving"], max_batch=4, max_len=256)
    cfg["check"] = {"min_served_tokens": 256, "max_requests": 16}
    mix = {"loop": "rate", "rate_per_s": 12.0, "output_points": 6,
           "prompt": {"median": 24, "sigma": 0.5, "min": 4, "max": 96},
           "output": {"median": 12, "sigma": 0.5, "min": 2, "max": 48}}
    return Cell(base.name, 1, cfg, mix, base.end_to_end, base.per_layer,
                TINY_LIMITS)


def _run(control=None):
    res, checks, _ = run.run_cell(_cell(), SEED, 3.0, False, "cpu",
                                  control=control,
                                  peaks={"bf16_flops": 1e12})
    return res, checks


def test_a_sound_run_is_correct():
    res, checks = _run()
    assert res["correct"], checks
    assert res["attempted"] == 36 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p90_s", "itl_p95_s", "setup_s"}
    assert checks["window_compiles"]["value"] == 0


def test_the_int8_control_is_not_correct():
    res, checks = _run(control="int8")
    assert not res["correct"]
    assert checks["mean_logit_gap"]["value"] > TINY_LIMITS["mean_logit_gap"]


def _unchanged(self, params, cache, state, *args):
    return cache, state


def _drop_odd_slots(method):
    def wrapped(self, params, cache, *args, block_tables=None, **kw):
        keep = (jnp.arange(block_tables.shape[0]) % 2 == 0)[:, None]
        return method(self, params, cache, *args,
                      block_tables=jnp.where(keep, block_tables, 0), **kw)
    return wrapped


def _altered_token(sample):
    return lambda *a: (sample(*a) + 1) % TINY["vocab_size"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch_dropped",
                                   "token_altered"])
def test_a_broken_step_is_not_correct(monkeypatch, fault):
    # no first token ever comes from a broken step: wait briefly for it
    monkeypatch.setattr(run, "DRAIN_S", 1.0)
    if fault == "state_unchanged":
        monkeypatch.setattr(ServingEngine, "_mixed_impl", _unchanged)
        monkeypatch.setattr(ServingEngine, "_decode_impl", _unchanged)
    elif fault == "half_batch_dropped":
        for name in ("mixed_step", "decode_step"):
            monkeypatch.setattr(Model, name,
                                _drop_odd_slots(getattr(Model, name)))
    else:
        monkeypatch.setattr(engine_mod, "sample_per_slot",
                            _altered_token(engine_mod.sample_per_slot))
    res, checks = _run()
    assert not res["correct"], (fault, checks)


def test_a_traced_run_keeps_the_program_trace(monkeypatch, tmp_path):
    import jax

    from bench import stats, trace
    from test_bench_program_trace import _events

    # the profiler is stubbed; the window's trace is the hand-built one
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(trace, "load_events", lambda _: _events())
    monkeypatch.setattr(run, "CACHE", tmp_path)
    kept = []

    class Kept(stats.Record):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            kept.append(self)
    monkeypatch.setattr(stats, "Record", Kept)
    res, _, diag = run.run_cell(_cell(), SEED, 3.0, True, "cpu",
                                peaks={"bf16_flops": 1e12})
    assert kept[0].program_trace.executions == {"_mixed_impl": 1}
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["kv_pool_ms.rate"] == pytest.approx(4.0)
    assert got["attn_core_ms.rate"] == pytest.approx(4.0)
    assert got["unscoped_ms.rate"] == pytest.approx(4.0)
    assert got["host_idle_ms.rate"] == pytest.approx(16.0)
    # matmul_ms reads attn.out and head, 4 ms each
    assert got["matmul_ms.rate"] == pytest.approx(8.0)
    assert any(line.startswith("self ms per run by scope") for line in diag)
    assert res["breakdown"]["device_ops"][0][0] == "fusion"


def test_cells_sharing_a_cache_each_read_their_own_hlo(monkeypatch, tmp_path,
                                                     capsys):
    """Two cells' traced runs in one checkout: each compiles its step
    programs itself, past the other cell's entries in the shared cache,
    and names its ops from its own dump alone."""
    import dataclasses
    import functools

    import jax

    from bench import trace
    from test_bench_program_trace import DEV, MS, _events

    # XLA reads its flags at its first compile, once a process: the dump
    # flags a run sets below leave this one's backend as it is, and only
    # the stand-in dumps of ``compiled`` appear
    jax.jit(lambda x: x + 1)(1)
    monkeypatch.setattr(run, "CACHE", tmp_path)
    monkeypatch.setattr(run, "SHARED", tmp_path / "jax")
    monkeypatch.setenv("XLA_FLAGS", "")
    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    # fusion.9 carries no op_name: only the run's dump names it
    events = [e for e in _events() if e[1] != "XLA Ops"] + [
        (DEV, "XLA Ops", "fusion.9", 6 * MS, 4 * MS, {"hlo_op": "fusion.9"})]
    monkeypatch.setattr(trace, "load_events", lambda _: events)
    monkeypatch.setattr(run, "find_chips",
                        lambda n: (jax.devices()[0], len(jax.devices())))
    monkeypatch.setattr(run, "run_cell", functools.partial(
        run.run_cell, peaks={"bf16_flops": 1e12}))
    views = []
    monkeypatch.setattr(run, "use_cache", views.append)
    (tmp_path / "jax").mkdir()
    for name in ("jit__mixed_impl-other-cache", "jit_gather-c-cache"):
        (tmp_path / "jax" / name).write_text("x")
    warm_up = run.warm_up

    def compiled(scope, key):
        """What XLA and JAX leave when the run compiles its mixed step:
        the dump, and the new cache entry in the run's view."""
        def warm(engine, mix, vocab):
            warm_up(engine, mix, vocab)
            hlo = Path(os.environ["XLA_FLAGS"].split("--xla_dump_to=")[-1]
                       .split()[0])
            hlo.mkdir(parents=True, exist_ok=True)
            (hlo / "module_0001.jit__mixed_impl.after_optimizations.txt"
             ).write_text('  %fusion.9 = bf16[4]{0} fusion(%p), metadata='
                          '{op_name="jit(_mixed_impl)/while/body/'
                          f'{scope}/dot_general"}}\n')
            assert sorted(p.name for p in views[-1].iterdir()) == [
                "jit_gather-c-cache"]
            (views[-1] / key).write_text("y")
        return warm

    read = {}
    for name, scope, key in [("a.rate", "attn.core", "jit__mixed_impl-a-cache"),
                             ("b.rate", "attn.kv_gather",
                              "jit__mixed_impl-b-cache")]:
        monkeypatch.setattr(run, "warm_up", compiled(scope, key))
        cell = dataclasses.replace(_cell(), name=name)
        run.serve(cell, SEED, 3.0, True)
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        read[name] = {k: v["value"] for k, v in out["metrics"].items()}
        assert not run.traced_dir(cell).exists()
    assert read["a.rate"]["attn_core_ms.rate"] == pytest.approx(4.0)
    assert read["a.rate"]["kv_pool_ms.rate"] == 0.0
    assert read["b.rate"]["kv_pool_ms.rate"] == pytest.approx(4.0)
    assert read["b.rate"]["attn_core_ms.rate"] == 0.0
    # each run's compiled entry joins the shared cache; no run's dir stays
    assert sorted(p.name for p in (tmp_path / "jax").iterdir()) == [
        "jit__mixed_impl-a-cache", "jit__mixed_impl-b-cache",
        "jit__mixed_impl-other-cache", "jit_gather-c-cache"]
    assert sorted(p.name for p in tmp_path.iterdir()) == ["jax", "traced"]
    assert not any((tmp_path / "traced").iterdir())


def test_every_cell_states_its_limits():
    for wl in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]:
        limits = load_cell(wl["name"]).limits
        assert limits and all(v > 0 for v in limits.values())
