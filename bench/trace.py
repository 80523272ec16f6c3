"""Reduce a profiler trace to device time, busy share and idle gaps.

A ``--trace 1`` run records the window with ``jax.profiler``.  The
benchmark's own host spans (``TraceAnnotation``) share the trace's
clock: ``bench.window`` marks the measured window, ``bench.step``,
``bench.submit`` and ``bench.sleep`` what the host was doing.  Device
events come from the TPU planes: ``XLA Modules`` holds one event per
execution of a compiled program (``jit_<name>(...)``), ``XLA Ops`` one
per operation.

Everything below ``load_events`` works on plain event tuples
``(plane, line, name, start_ns, duration_ns[, stats])``, so a hand-built
list checks the arithmetic without a trace file.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from dataclasses import dataclass

DEVICE_PREFIX = "/device:TPU:"
MODULES, OPS = "XLA Modules", "XLA Ops"
WINDOW_SPAN = "bench.window"
SLEEP_SPAN = "bench.sleep"
HOST_PREFIXES = ("engine.", "bench.")


def load_events(trace_dir: str) -> list[tuple]:
    """The device planes' module and op events and the host's
    ``engine.*``/``bench.*`` spans of the newest ``.xplane.pb`` under
    ``trace_dir``, each with its stats."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise RuntimeError(f"no trace written under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (MODULES, OPS):
                continue
            for ev in line.events:
                if device or ev.name.startswith(HOST_PREFIXES):
                    out.append((plane.name, line.name, ev.name,
                                float(ev.start_ns), float(ev.duration_ns),
                                {k: str(v) for k, v in ev.stats}))
    return out


def op_name(text: str) -> str:
    """``%copy.7 = bf16[4,8]{1,0} copy(...)`` -> ``%copy.7 = bf16[4,8] copy``:
    an HLO instruction without its layouts and operands."""
    text = re.sub(r"\{[^}]*\}", "", text)
    m = re.match(r"(\S+) = (.*?)\s*([a-z][\w\-]*)\(", text)
    if m is None:
        return text[:120]
    return f"{m.group(1)} = {m.group(2)[:80]} {m.group(3)}"


def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a, b) -> list[tuple[float, float]]:
    """Intersection of two disjoint sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def self_times(ops) -> list[float]:
    """Self time of each ``(start, end)`` op of one device line: its
    length less the union of the ops nested in it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    own = [e - s for s, e in ops]
    covered = [s for s, _ in ops]     # each op's children cover up to here
    stack: list[int] = []
    for i in order:
        s, e = ops[i]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            hi = min(e, ops[p][1])
            lo = max(s, covered[p])
            if hi > lo:
                own[p] -= hi - lo
                covered[p] = hi
        stack.append(i)
    return own


@dataclass
class Reduced:
    """One traced window, in nanoseconds on the trace's clock."""

    window: tuple[float, float]
    devices: list[str]
    busy: dict            # device -> merged busy intervals in the window
    modules: list[tuple]  # (device, name, start, duration) in the window
    ops: dict             # op name -> self ns in the window, summed
                          # over devices (a loop less the ops in it)
    spans: list[tuple]    # (name, start, end) host spans of the benchmark

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self, within=None) -> float:
        """Busy seconds averaged over the devices, optionally only
        inside the intervals ``within``."""
        tot = 0.0
        for iv in self.busy.values():
            tot += length(iv if within is None else intersect(iv, within))
        return tot / max(len(self.busy), 1) / 1e9

    def program(self, pattern: str) -> tuple[float, int]:
        """(device seconds, executions) of the programs whose module name
        contains ``pattern``, averaged over the devices."""
        hits = [d for _, name, _, d in self.modules if pattern in name]
        n_dev = max(len(self.devices), 1)
        return sum(hits) / n_dev / 1e9, len(hits) // n_dev

    def occupied(self) -> list[tuple[float, float]]:
        """The window less the host's sleeps between arrivals."""
        sleeps = merge((s, e) for n, s, e in self.spans if n == SLEEP_SPAN)
        out, cur = [], self.window[0]
        for s, e in clip(sleeps, *self.window):
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
        if cur < self.window[1]:
            out.append((cur, self.window[1]))
        return out

    def idle_gaps(self, within=None, top: int = 10) -> list[list]:
        """The longest device-idle gaps, each named by the host span that
        covers most of it."""
        within = within or [self.window]
        gaps = []
        for iv in self.busy.values():
            for ws, we in within:
                cur = ws
                for s, e in clip(iv, ws, we):
                    if s > cur:
                        gaps.append((cur, s))
                    cur = max(cur, e)
                if cur < we:
                    gaps.append((cur, we))
        named = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
            cover = defaultdict(float)
            for n, hs, he in self.spans:
                if n != WINDOW_SPAN:
                    cover[n] += max(0.0, min(e, he) - max(s, hs))
            label = max(cover, key=cover.get) if cover and \
                max(cover.values()) > 0 else "host"
            named.append([label, (e - s) / 1e9])
        return named

    def top_ops(self, top: int = 10) -> list[list]:
        """The ops with the most self time, in seconds per device."""
        n_dev = max(len(self.devices), 1)
        best = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns / n_dev / 1e9] for name, ns in best]


def reduce_events(events: list[tuple]) -> Reduced:
    """Cut the events to the ``bench.window`` span and reduce them."""
    spans = [(n, s, s + d) for _, _, n, s, d, *_ in events
             if n.startswith("bench.")]
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win:
        raise RuntimeError("the trace holds no bench.window span")
    lo, hi = win[-1]
    devices = sorted({p for p, *_ in events if p.startswith(DEVICE_PREFIX)})
    op_iv, mod_iv = defaultdict(list), defaultdict(list)
    modules, ops = [], defaultdict(float)
    for plane, line, name, s, d, *_ in events:
        if not plane.startswith(DEVICE_PREFIX):
            continue
        if line == OPS:
            op_iv[plane].append((s, s + d, name))
        elif line == MODULES:
            mod_iv[plane].append((s, s + d))
            if lo <= s < hi:
                modules.append((plane, name, s, d))
    for plane in devices:
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in op_iv[plane]
                  if s < hi and e > lo]
        for (_, _, name), t in zip(inside, self_times(
                [(s, e) for s, e, _ in inside])):
            ops[op_name(name)] += t
    # busy is the union of operations; a plane without an ops line
    # falls back to its program executions
    busy = {p: clip(merge([(s, e) for s, e, _ in op_iv[p]] or mod_iv[p]),
                    lo, hi) for p in devices}
    return Reduced((lo, hi), devices, busy, modules, dict(ops), spans)
