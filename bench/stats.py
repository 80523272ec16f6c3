"""Metric arithmetic over one run's lifecycle events.

The engine publishes ``submit``, ``admit``, ``first_token``,
``progress``, ``finish`` and ``preempt`` events (``serving.events``);
``progress`` comes once per occupied slot at every harvest, after the
harvest's blocking read, so its wall stamp marks tokens that exist.
``Record`` holds those events with the benchmark's own view of each
request (when it was due) and the window's bounds, all on the
``time.perf_counter`` clock.

Definitions:

* TTFT - from a request's due time to the first ``progress`` with
  ``count >= 1``, for every request due in the window.  The run keeps
  stepping past the close, with no new arrivals, until each has its
  first token (or a minute has passed); one still without a token then
  enters at its wait so far.
* ITL - for consecutive ``progress`` events of one request with counts
  ``1 <= c0 < c1`` inside the window, ``c1 - c0`` gaps of
  ``(t1 - t0) / (c1 - c0)``.
* Percentiles are nearest-rank: ``sorted(xs)[ceil(q / 100 * n) - 1]``.
"""
from __future__ import annotations

import math
from collections import defaultdict
from dataclasses import dataclass, field


@dataclass
class Record:
    """What a run leaves for its metric readers."""

    events: list            # (kind, uid, step, t, data) in emission order
    due: dict               # uid -> wall time the request was due
    prompt_len: dict        # uid -> prompt tokens
    t0: float               # window open
    t1: float               # window close
    t_drained: float        # end of the wait for first tokens (>= t1)
    loop: str               # "rate" | "batch"
    max_batch: int
    config: dict            # the configuration file
    trace: object = None    # bench.trace.Reduced in a --trace 1 run
    # bench.program_trace.ProgramTrace of the same trace
    program_trace: object = None
    family: object = None   # the configuration's bench/families module
    peaks: dict = field(default_factory=dict)   # bench/peaks.json entry
    setup_s: float = 0.0    # process start to the end of warm-up
    # tokens resident in the KV pool at t0 and t1 (engine.memory_stats)
    pool_tokens: tuple[int, int] = (0, 0)

    def of(self, kind: str) -> list:
        return [e for e in self.events if e[0] == kind]


def percentile(xs, q: float):
    """Nearest-rank percentile; ``None`` on an empty sample."""
    if not xs:
        return None
    ys = sorted(xs)
    return ys[max(math.ceil(q / 100.0 * len(ys)), 1) - 1]


def window_uids(rec: Record) -> list[int]:
    """Requests due inside the window."""
    return [u for u, t in rec.due.items() if rec.t0 <= t < rec.t1]


def ttfts(rec: Record) -> list[float]:
    first = {}
    for _, uid, _, t, data in rec.of("progress"):
        if data["count"] >= 1 and uid not in first:
            first[uid] = t
    return [first.get(u, rec.t_drained) - rec.due[u]
            for u in window_uids(rec)]


def itl_samples(rec: Record) -> list[float]:
    last: dict[int, tuple[int, float]] = {}
    out: list[float] = []
    for _, uid, _, t, data in rec.of("progress"):
        c = data["count"]
        prev = last.get(uid)
        last[uid] = (c, t)
        if prev is None or not rec.t0 <= prev[1] or t > rec.t1:
            continue
        c0, t0 = prev
        if c0 >= 1 and c > c0:
            out += [(t - t0) / (c - c0)] * (c - c0)
    return out


def output_tokens(rec: Record) -> int:
    """Tokens harvested inside the window (count increments whose
    ``progress`` stamp lies in it)."""
    last: dict[int, int] = defaultdict(int)
    n = 0
    for kind, uid, _, t, data in rec.events:
        if kind == "preempt":
            last[uid] = 0
        if kind != "progress":
            continue
        c = data["count"]
        if rec.t0 <= t <= rec.t1:
            n += max(c - last[uid], 0)
        last[uid] = c
    return n


def prompt_tokens(rec: Record) -> int:
    """Prompt tokens prefilled inside the window.  Every token the pool
    gains is a prompt token or a decode lane's token: the pool's tokens
    at t1, less those at t0, plus what requests finished in the window
    held when they left (prompt + output - 1), less one per decode lane
    (a count increment from a count of 1 or more)."""
    last: dict[int, int] = defaultdict(int)
    decode, left = 0, 0
    for kind, uid, _, t, data in rec.events:
        if kind == "preempt":
            last[uid] = 0
        if not rec.t0 <= t <= rec.t1:
            if kind == "progress":
                last[uid] = data["count"]
            continue
        if kind == "progress":
            c0, c = last[uid], data["count"]
            if c0 >= 1 and c > c0:
                decode += c - c0
            last[uid] = c
        elif kind == "finish":
            left += rec.prompt_len[uid] + data["n_generated"] - 1
    return rec.pool_tokens[1] - rec.pool_tokens[0] + left - decode


def queue_waits(rec: Record) -> list[float]:
    """``submit`` to ``admit`` for requests submitted in the window; one
    still waiting at the close enters at its wait so far."""
    sub = {uid: t for _, uid, _, t, _ in rec.of("submit")
           if rec.t0 <= t <= rec.t1}
    adm = {}
    for _, uid, _, t, _ in rec.of("admit"):
        adm.setdefault(uid, t)
    return [min(adm.get(u, rec.t1), rec.t1) - t for u, t in sub.items()]


def window_steps(rec: Record) -> dict[int, list]:
    """Dispatch index -> the ``progress`` events of its harvest, for the
    dispatches harvested inside the window.  Every dispatch harvests
    every occupied slot, so each one appears."""
    out: dict[int, list] = defaultdict(list)
    for ev in rec.of("progress"):
        if rec.t0 <= ev[3] <= rec.t1:
            out[ev[2] - 1].append(ev)
    return dict(out)


def slot_occupancy(rec: Record) -> float | None:
    steps = window_steps(rec)
    if not steps:
        return None
    occ = [len(evs) for evs in steps.values()]
    return sum(occ) / len(occ) / rec.max_batch
