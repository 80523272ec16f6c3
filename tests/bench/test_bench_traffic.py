"""The traffic generator: fixed work per seed, seeded order."""
import sys
from pathlib import Path

# the benchmark lives beside src/, outside the package path
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import json
from collections import Counter

import pytest

from bench import traffic

MIXES = Path(__file__).resolve().parents[2] / "bench" / "traffic"
BIG = 2**31 + 12345   # the driver's seeds exceed 32 signed bits


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["chat.rate", "code.batch",
                                  "longgen.batch"])
def test_same_seed_same_bytes(name):
    mix = _mix(name)
    a = traffic.requests(mix, BIG, 30.0, 16)
    b = traffic.requests(mix, BIG, 30.0, 16)
    assert a == b
    assert traffic.prompt_tokens(a[3], BIG, 1000) == \
        traffic.prompt_tokens(b[3], BIG, 1000)


@pytest.mark.parametrize("name", ["chat.rate", "code.batch"])
def test_other_seed_other_order_same_sizes(name):
    mix = _mix(name)
    # max_batch 0: no residual head, the mix's sizes as drawn
    a = traffic.requests(mix, BIG, 30.0, 0)
    b = traffic.requests(mix, BIG + 1, 30.0, 0)
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]
    assert traffic.prompt_tokens(a[20], BIG, 1000) != \
        traffic.prompt_tokens(b[20], BIG + 1, 1000)
    for field in ("prompt_len", "output_len"):
        assert Counter(getattr(r, field) for r in a) == \
            Counter(getattr(r, field) for r in b)


def test_open_loop_arrivals_fill_the_window():
    mix = dict(_mix("chat.rate"), rate_per_s=5.0)
    reqs = traffic.requests(mix, BIG, 30.0, 32)
    assert len(reqs) == 150
    due = [r.due for r in reqs]
    assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 30.0


def test_backlog_head_takes_residual_lengths():
    mix = _mix("longgen.batch")
    reqs = traffic.requests(mix, BIG, 30.0, 16)
    full = traffic.requests(mix, BIG, 30.0, 0)
    grid = traffic.output_lengths(mix)
    for r, f in zip(reqs[:16], full[:16]):
        assert 1 <= r.prompt_len <= f.prompt_len
        assert r.output_len in grid
        assert r.output_len <= f.output_len or r.output_len == grid[0]
    assert sum(r.output_len for r in reqs[:16]) < \
        0.75 * sum(f.output_len for f in full[:16])
    assert reqs[16:] == full[16:]


@pytest.mark.parametrize("name", ["chat.rate", "code.batch",
                                  "longgen.batch"])
def test_sizes_stay_inside_the_mix(name):
    mix = _mix(name)
    for r in traffic.requests(mix, BIG, 30.0, 16):
        assert r.output_len in traffic.output_lengths(mix)
    for r in traffic.requests(mix, BIG, 30.0, 16)[16:]:
        assert mix["prompt"]["min"] <= r.prompt_len <= mix["prompt"]["max"]
    assert len(traffic.output_lengths(mix)) <= mix["output_points"]


def test_quantile_grid_follows_the_lognormal():
    grid = traffic.quantile_grid({"median": 100, "sigma": 0.5, "min": 1,
                                  "max": 10**6}, 101)
    assert grid[50] == 100 and grid == sorted(grid)
    assert grid[0] >= 1 and grid[-1] > 250
