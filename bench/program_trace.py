"""Reduce a profiler trace to what the program marks in it.

``bench/trace.py`` times a ``--trace 1`` window from outside the
program: the benchmark's own spans, programs by their jit names, ops by
their HLO names.  This module reads the same ``.xplane.pb`` for the
marks the serving program leaves itself (``repro.serving.events``):

* the engine's host spans (``SPAN_NAMES``: ``engine.step``,
  ``engine.admit``, ``engine.dispatch``, ``engine.harvest.wait``, ...),
  on the device planes' clock;
* each step-program op's self time - its duration less the part that
  ops nested in it cover (the layer scan's ``while`` holds its body) -
  given to the innermost named scope (``SCOPE_NAMES``) in the op's
  ``op_name`` metadata, or to ``unscoped``;
* the device's idle time, split by the innermost host span over it.

Everything here but ``load_hlo`` works on plain tuples
``(plane, line, name, start_ns, duration_ns, stats)``
(``bench.trace.load_events``), so a hand-built list checks the
arithmetic without a trace file.  Nothing here imports the program: the
scope names come in as an argument.  ``bench/run.py --trace 1`` keeps
the reduction in its record (``Record.program_trace``), where the
metric files read it.

    python -m bench.program_trace --workload <cell> --seed <n> --seconds <s>

runs the cell as ``bench/run.py --trace 1`` does and reports the
end-to-end metrics of the traced run beside the per-layer ones, so that
a traced and an untraced run of one seed give the cost of tracing.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path

from bench.trace import (DEVICE_PREFIX, MODULES, WINDOW_SPAN, clip, length,
                         merge, self_times)

STEP_PROGRAMS = ("_mixed_impl", "_decode_impl")
# the op stats that carry the HLO ``op_name`` metadata
OP_NAME_STATS = ("tf_op", "op_name")
UNSCOPED = "unscoped"

# metric groups (per step-program execution, or per engine step)
KV_POOL = ("attn.kv_write", "attn.kv_gather")
ATTN_CORE = ("attn.core",)
MATMUL = ("attn.qkv", "attn.out", "ffn", "head")
# every span of ``ServingEngine.step``: the idle gap between two step
# programs opens under the harvest's read-back and closes in the next
# dispatch, and the profiler's host and device clocks can place its two
# ends up to ~0.7 ms apart from one run to the next (PERF.md, section 5)
ENGINE_STEP = ("engine.step", "engine.admit", "engine.capacity",
               "engine.dispatch", "engine.harvest", "engine.harvest.wait",
               "engine.harvest.fetch")


def hlo_op_names(text: str) -> dict[str, str]:
    """Instruction name -> ``op_name`` metadata in one optimized HLO
    module's text (``--xla_dump_to``)."""
    return dict(re.findall(
        r'^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*?op_name="([^"]*)"', text,
        re.M))


def load_hlo(dump_dir: Path) -> dict[str, dict[str, str]]:
    """Step program -> ``hlo_op_names`` of the optimized HLO that XLA
    wrote under ``dump_dir`` when it compiled the program."""
    return {prog: {k: v for path in sorted(dump_dir.glob(
                f"*{prog}*after_optimizations.txt"))
                for k, v in hlo_op_names(path.read_text()).items()}
            for prog in STEP_PROGRAMS}


def op_name_of(name: str, stats: dict, hlo: dict) -> str:
    """An op event's ``op_name``: its own stat, else the metadata of its
    instruction (``%copy.7 = ...`` or ``copy.7``) in ``hlo``."""
    for key in OP_NAME_STATS:
        if key in stats:
            return stats[key]
    instr = stats.get("hlo_op") or name.split(" = ")[0].lstrip("%")
    return hlo.get(instr, "")


def innermost_scope(op_name: str, scopes) -> str:
    """The last component of ``op_name`` that names a scope."""
    for part in reversed(op_name.split("/")):
        if part in scopes:
            return part
    return UNSCOPED


def label_idle(idle, spans, outside: str = "host") -> dict[str, float]:
    """Nanoseconds of the disjoint sorted ``idle`` intervals under each
    label: the innermost (latest-opened) of the nested ``spans``
    ``(name, start, end)`` over them, else ``outside``."""
    spans = [sp for sp in spans if sp[2] > sp[1]]
    cuts = sorted({t for _, s, e in spans for t in (s, e)}
                  | {t for s, e in idle for t in (s, e)})
    starts = defaultdict(list)
    ends = defaultdict(list)
    for k, (_, s, e) in enumerate(spans):
        starts[s].append(k)
        ends[e].append(k)
    out: dict[str, float] = defaultdict(float)
    active: set[int] = set()
    j = 0
    for a, b in zip(cuts, cuts[1:]):
        active -= set(ends.get(a, ()))
        active |= set(starts.get(a, ()))
        while j < len(idle) and idle[j][1] <= a:
            j += 1
        if j == len(idle):
            break
        lo, hi = max(a, idle[j][0]), min(b, idle[j][1])
        if hi <= lo:
            continue
        name = outside
        if active:
            k = max(active, key=lambda k: (spans[k][1], -spans[k][2]))
            name = spans[k][0]
        out[name] += hi - lo
    return dict(out)


@dataclass
class ProgramTrace:
    """One traced window as the program marks it, in nanoseconds
    averaged over the devices."""

    window: tuple[float, float]
    executions: dict    # step program -> executions starting in the window
    module_ns: dict     # step program -> device time of those executions
    self_ns: dict       # (step program, scope) -> op self time
    host_ns: dict       # span name -> host time inside the window
    steps: int          # engine.step spans inside the window
    idle_ns: dict       # innermost host span -> device idle time
    gaps: list          # [label, seconds] of the longest idle gaps
    no_op_name_ns: float = 0.0   # step-program self time of ops with no
                                 # op_name at all (part of unscoped)

    def scope_ms(self, scopes, program: str | None = None) -> float | None:
        """Self ms under ``scopes`` per execution of ``program``; with no
        program, the mean of that over the step programs that ran, so that
        each program counts alike whatever mix of them a seed drew."""
        ms = [sum(v for (p, sc), v in self.self_ns.items()
                  if p == prog and sc in scopes) / n / 1e6
              for prog, n in self.executions.items()
              if n and program in (None, prog)]
        return sum(ms) / len(ms) if ms else None

    def idle_ms_per_step(self, labels) -> float | None:
        if not self.steps or not self.idle_ns:    # no step, or no device
            return None
        return sum(self.idle_ns.get(n, 0.0) for n in labels) \
            / self.steps / 1e6


def reduce_program(events: list[tuple], scopes,
                   hlo: dict | None = None) -> ProgramTrace:
    """Cut the events to the ``bench.window`` span and reduce them.
    ``hlo`` maps a step program to ``hlo_op_names`` of its optimized
    HLO, for op events that carry no ``op_name`` stat."""
    hlo = hlo or {}
    spans = [(n, s, s + d) for p, _, n, s, d, _ in events
             if not p.startswith(DEVICE_PREFIX)]
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win:
        raise RuntimeError("the trace holds no bench.window span")
    lo, hi = win[-1]
    devices = sorted({p for p, *_ in events if p.startswith(DEVICE_PREFIX)})
    n_dev = max(len(devices), 1)
    mods, ops = defaultdict(list), defaultdict(list)
    for plane, line, name, s, d, st in events:
        if plane.startswith(DEVICE_PREFIX):
            (mods if line == MODULES else ops)[plane].append(
                (s, s + d, name, st))

    executions, module_ns = defaultdict(float), defaultdict(float)
    self_ns = defaultdict(float)
    no_op_name = 0.0
    idle_ns: dict[str, float] = defaultdict(float)
    gaps = []
    held = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    for dev in devices:
        # the step-program executions that start in the window
        runs = sorted((s, e, prog) for s, e, name, _ in mods[dev]
                      for prog in STEP_PROGRAMS
                      if prog in name and lo <= s < hi)
        for s, e, prog in runs:
            executions[prog] += 1 / n_dev
            module_ns[prog] += (e - s) / n_dev
        starts = [r[0] for r in runs]
        dev_ops = ops[dev]
        own = self_times([(s, e) for s, e, _, _ in dev_ops])
        for (s, e, name, st), t in zip(dev_ops, own):
            k = bisect.bisect_right(starts, s) - 1
            if k < 0 or s >= runs[k][1]:
                continue                  # not inside a step program
            prog = runs[k][2]
            op = op_name_of(name, st, hlo.get(prog, {}))
            self_ns[(prog, innermost_scope(op, scopes))] += t / n_dev
            no_op_name += 0.0 if op else t / n_dev
        # idle: the window less the union of ops (else of executions)
        busy = clip(merge((s, e) for s, e, *_ in (dev_ops or mods[dev])),
                    lo, hi)
        idle, cur = [], lo
        for s, e in busy:
            if s > cur:
                idle.append((cur, s))
            cur = max(cur, e)
        if cur < hi:
            idle.append((cur, hi))
        for name, ns in label_idle(idle, held).items():
            idle_ns[name] += ns / n_dev
        for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:10]:
            by = label_idle([(s, e)], held)
            gaps.append([max(by, key=by.get), (e - s) / 1e9])

    host_ns = defaultdict(float)
    for name, s, e in held:
        if name.startswith("engine."):
            host_ns[name] += length(clip([(s, e)], lo, hi))
    steps = sum(n == "engine.step" and lo <= s < hi for n, s, _ in held)
    gaps.sort(key=lambda g: -g[1])
    return ProgramTrace((lo, hi), dict(executions), dict(module_ns),
                        dict(self_ns), dict(host_ns), steps, dict(idle_ns),
                        gaps[:10], no_op_name)


def scope_ms(pt: ProgramTrace | None, scopes) -> float | None:
    return None if pt is None else pt.scope_ms(scopes)


def unscoped_ms(pt: ProgramTrace | None) -> float | None:
    return scope_ms(pt, (UNSCOPED,))


def host_idle_ms(pt: ProgramTrace | None) -> float | None:
    """Device-idle ms per engine step while the host was inside
    ``ServingEngine.step``: the round trip from one step program's end to
    the next one's start (reading back, admission, reservation, dispatch),
    whatever span the trace puts each part of the gap under."""
    return None if pt is None else pt.idle_ms_per_step(ENGINE_STEP)


def roofline_share(pt: ProgramTrace | None, scopes, flops: float,
                   bytes: float, peaks: dict) -> float | None:
    """Share of its roofline, in %, that the ops under ``scopes`` reach:
    the least time the chip needs for ``flops`` operations and ``bytes``
    of HBM traffic per step-program execution (the larger of flops over
    the bf16 peak and bytes over HBM bandwidth), over the scopes' device
    self time per execution; both averaged over the step programs alike,
    as ``scope_ms`` does.  ``None`` where no such op ran."""
    ms = scope_ms(pt, scopes)
    if not ms:
        return None
    least = max(flops / peaks["bf16_flops"], bytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)


def diag(pt: ProgramTrace) -> list[str]:
    """stderr lines: host and device-idle ms per step by span, device ms
    per execution by scope, and the longest idle gaps by span."""
    n = max(pt.steps, 1)
    names = sorted(set(pt.host_ns) | set(pt.idle_ns))
    runs = sum(pt.executions.values())
    scopes = sorted({sc for _, sc in pt.self_ns})
    own = sum(pt.self_ns.values())
    return [
        f"program trace: {pt.steps} engine steps; host ms per step by span: "
        + ", ".join(f"{k} {pt.host_ns[k] / n / 1e6:.3f}"
                    for k in names if k in pt.host_ns),
        "device idle ms per step by innermost span: " + ", ".join(
            f"{k} {pt.idle_ns[k] / n / 1e6:.3f}"
            for k in names if k in pt.idle_ns),
        "step programs: " + ", ".join(
            f"{p} {pt.executions[p]:.0f} runs of "
            f"{pt.module_ns[p] / pt.executions[p] / 1e6:.3f} ms"
            for p in sorted(pt.executions))
        + f"; op self time per run {own / max(runs, 1) / 1e6:.3f} ms, of "
        f"which ops with no op_name {pt.no_op_name_ns / max(runs, 1) / 1e6:.3f}",
        "self ms per run by scope: " + ", ".join(
            f"{p}/{sc} {pt.self_ns[(p, sc)] / max(pt.executions[p], 1) / 1e6:.3f}"
            for p in sorted(pt.executions) for sc in scopes
            if (p, sc) in pt.self_ns),
        "longest idle gaps s by span: " + ", ".join(
            f"{g[0]} {g[1]:.6f}" for g in pt.gaps),
    ]


def main(argv=None) -> None:
    import argparse
    import dataclasses

    from bench import run
    from bench.manifest import load_cell

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    run.serve(dataclasses.replace(cell, per_layer=cell.per_layer
                                  + cell.end_to_end),
              args.seed, args.seconds, trace=True)


if __name__ == "__main__":
    main()
