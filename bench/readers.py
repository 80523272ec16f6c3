"""Shared arithmetic of the per-layer metric files in ``bench/metrics``.

Each reader returns ``None`` where the run left nothing to read (no
trace, no execution of the program, no FLOPs), and the harness then
leaves the metric out of the result line.
"""
from __future__ import annotations

from bench import flops
from bench.trace import length

MIXED, DECODE = "_mixed_impl", "_decode_impl"   # the engine's step programs


def step_ms(rec, pattern: str) -> float | None:
    """Device milliseconds per execution of the programs ``pattern`` names."""
    if rec.trace is None:
        return None
    sec, n = rec.trace.program(pattern)
    return 1e3 * sec / n if n else None


def mfu(rec, programs: dict[str, str]) -> float | None:
    """Model FLOPs of the window's dispatches of the given kinds over the
    device time of their programs times the bf16 peak, in %.
    ``programs`` maps a dispatch kind ("mixed"/"decode") to its pattern."""
    if rec.trace is None:
        return None
    work = sum(f for kind, f in flops.step_flops(rec).values()
               if kind in programs)
    sec = sum(rec.trace.program(p)[0] for p in programs.values())
    if not work or not sec:
        return None
    return 100.0 * work / (sec * rec.peaks["bf16_flops"])


def idle_share(rec, occupied_only: bool) -> float | None:
    """Share of the window with no operation on the device, in %.  With
    ``occupied_only`` the host's sleeps between arrivals are left out."""
    if rec.trace is None:
        return None
    within = rec.trace.occupied() if occupied_only else [rec.trace.window]
    span = length(within) / 1e9
    if span <= 0:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s(within) / span)
