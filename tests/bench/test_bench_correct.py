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


def test_every_cell_states_its_limits():
    for wl in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]:
        limits = load_cell(wl["name"]).limits
        assert limits and all(v > 0 for v in limits.values())
