"""End-to-end and scheduler metric arithmetic on hand-written event lists."""
import sys
from pathlib import Path

# the benchmark lives beside src/, outside the package path
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest

from bench import stats
from bench.stats import Record


def _rec(events, due, t0=10.0, t1=20.0, loop="rate", max_batch=4,
         t_drained=None):
    return Record(events=events, due=due, prompt_len={u: 8 for u in due},
                  t0=t0, t1=t1, t_drained=t1 if t_drained is None
                  else t_drained, loop=loop, max_batch=max_batch, config={})


def _p(uid, step, t, count):
    return ("progress", uid, step, t, {"count": count})


def test_percentile_is_nearest_rank():
    assert stats.percentile([], 90) is None
    xs = list(range(1, 11))
    assert stats.percentile(xs, 90) == 9
    assert stats.percentile(xs, 95) == 10
    assert stats.percentile([3.0], 50) == 3.0


def test_ttft_counts_from_due_time_and_waits_past_the_close():
    events = [("submit", 1, 0, 10.5, {}), _p(1, 1, 11.0, 0),
              _p(1, 2, 11.4, 1),
              ("submit", 2, 2, 12.2, {}), _p(2, 3, 13.0, 0),
              # request 3 gets its first token after the close at t=20
              ("submit", 3, 3, 18.0, {}), _p(3, 4, 19.0, 0),
              _p(3, 5, 21.5, 1),
              # due before the window: not in the sample
              _p(4, 4, 19.5, 1)]
    due = {1: 10.2, 2: 12.0, 3: 17.5, 4: 9.0}
    got = sorted(stats.ttfts(_rec(events, due, t_drained=25.0)))
    # request 2 never gets one: it enters at 25.0 - 12.0
    assert got == pytest.approx([1.2, 4.0, 13.0])
    assert stats.percentile(got, 90) == pytest.approx(13.0)


def test_itl_splits_a_harvest_into_equal_gaps():
    events = [_p(1, 1, 11.0, 0), _p(1, 2, 11.5, 1),   # first token: no gap
              _p(1, 3, 11.6, 2),                      # 1 gap of 0.1
              _p(1, 4, 12.2, 5),                      # 3 gaps of 0.2
              _p(1, 5, 12.3, 5),                      # no new token
              _p(1, 6, 12.5, 6),                      # 0.2 since 12.3
              _p(1, 7, 20.5, 7)]                      # after the close
    got = stats.itl_samples(_rec(events, {1: 10.5}))
    assert got == pytest.approx([0.1, 0.2, 0.2, 0.2, 0.2])


def test_itl_pairs_must_start_inside_the_window():
    events = [_p(1, 1, 9.0, 3), _p(1, 2, 10.5, 4), _p(1, 3, 10.7, 5)]
    assert stats.itl_samples(_rec(events, {1: 8.0})) == pytest.approx([0.2])


def test_output_tokens_count_increments_inside_the_window():
    events = [_p(1, 1, 9.0, 3),               # before t0: baseline only
              _p(1, 2, 10.5, 5), _p(2, 2, 10.5, 1),
              _p(1, 3, 11.0, 6), ("finish", 1, 3, 11.0, {}),
              _p(2, 3, 21.0, 4)]              # after t1
    assert stats.output_tokens(_rec(events, {1: 0.0, 2: 0.0})) == 4


def test_queue_wait_and_occupancy():
    events = [("submit", 1, 0, 10.0, {}), ("submit", 2, 0, 10.0, {}),
              ("submit", 3, 0, 15.0, {}),
              ("admit", 1, 0, 10.1, {}), ("admit", 2, 0, 10.4, {}),
              _p(1, 1, 10.6, 0), _p(2, 1, 10.6, 0),
              _p(1, 2, 10.8, 1), _p(2, 2, 10.8, 0),
              _p(1, 3, 11.0, 2)]
    rec = _rec(events, {1: 10.0, 2: 10.0, 3: 15.0})
    # request 3 is never admitted: it enters at 20.0 - 15.0
    assert sorted(stats.queue_waits(rec)) == pytest.approx([0.1, 0.4, 5.0])
    # dispatches 0, 1, 2 held 2, 2 and 1 slots of 4
    assert stats.slot_occupancy(rec) == pytest.approx(5 / 3 / 4)


def test_prompt_tokens_from_pool_occupancy():
    # request 1 (prompt 8) is mid-prefill at t0 with 4 tokens in the pool,
    # finishes its prompt and two decode steps, and leaves with 3 tokens;
    # request 2 (prompt 8) is admitted and prefills 6 tokens by t1
    events = [_p(1, 1, 9.5, 0),
              _p(1, 2, 10.5, 1), _p(2, 2, 10.5, 0),
              _p(1, 3, 11.0, 2), _p(2, 3, 11.0, 0),
              _p(1, 4, 11.5, 3), ("finish", 1, 4, 11.5, {"n_generated": 3})]
    rec = _rec(events, {1: 9.0, 2: 10.2})
    rec.pool_tokens = (4, 6)
    # pool gained 2, request 1 left with 8 + 3 - 1 = 10, 2 decode rows:
    # prompt tokens = 2 + 10 - 2 = 10 = request 1's last 4 + request 2's 6
    assert stats.prompt_tokens(rec) == 10
