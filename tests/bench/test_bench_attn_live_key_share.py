"""The live-key share of latent attention, read from ``dispatch`` events."""
import sys
from pathlib import Path

# the benchmark lives beside src/, outside the package path
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest

from bench import manifest
from bench.stats import Record

NAME = "attn_live_key_share.batch"


def _rec(events=()):
    return Record(events=list(events), due={}, prompt_len={}, t0=10.0,
                  t1=20.0, t_drained=20.0, loop="batch", max_batch=4,
                  config={})


def _dispatch(t, program, keys, live):
    return ("dispatch", -1, 0, t, {"program": program, "keys": keys,
                                   "live_keys": live})


def test_share_over_the_window_dispatches():
    rec = _rec([_dispatch(9.0, "mixed", 4096, 4096),   # before the window
                _dispatch(11.0, "mixed", 4096, 1024),
                _dispatch(12.0, "decode", 4096, 512),
                _dispatch(13.0, "decode", 2048, 512),
                _dispatch(21.0, "decode", 4096, 4096)])  # after it
    assert manifest.metric_reader(NAME)(rec) == pytest.approx(
        100.0 * 2048 / 10240)


@pytest.mark.parametrize("events", [
    [],
    # dispatch events that carry no key counts, as a program without the
    # latent attention kernel writes them
    [("dispatch", -1, 0, 11.0, {"program": "mixed", "lanes": 64,
                                "live_lanes": 20})],
], ids=["no_events", "no_key_fields"])
def test_nothing_to_read_gives_none(events):
    assert manifest.metric_reader(NAME)(_rec(events)) is None
