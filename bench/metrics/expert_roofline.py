"""Share of its roofline, in %, that the held experts' grouped matmuls
(scope ``moe.experts``) reach per step-program execution.

The work comes from the engine's ``experts`` events (one a harvest: the
(token, expert) rows the held experts computed and the experts hit since
the previous harvest, summed over the MoE layers), each harvest's totals
shared among the dispatches it covers.  Per execution of each step
program, the configuration's family gives the FLOPs (``expert_flops``:
gate, up and down over the rows) and the least bytes (``expert_bytes``:
every hit expert's weights once, each row in and out); both are averaged
over the two programs as their device time is (``roofline_share``).
None where the run has no such events or no ``moe.experts`` op ran.
"""
from collections import defaultdict

from bench.program_trace import roofline_share

PROGRAMS = {"mixed": "_mixed_impl", "decode": "_decode_impl"}


def work_per_execution(rec) -> dict:
    """Step program -> (rows, experts hit) per execution of it, over the
    harvests inside the window."""
    pending, runs = [], defaultdict(float)
    rows, hit = defaultdict(float), defaultdict(float)
    for kind, _, _, t, data in rec.events:
        if kind == "dispatch":
            pending.append(PROGRAMS[data["program"]])
        elif kind == "experts":
            if pending and rec.t0 <= t <= rec.t1:
                for prog in pending:
                    runs[prog] += 1
                    rows[prog] += data["expert_rows"] / len(pending)
                    hit[prog] += data["experts_hit"] / len(pending)
            pending = []
    return {p: (rows[p] / n, hit[p] / n) for p, n in runs.items()}


def read(rec):
    pt = getattr(rec, "program_trace", None)
    fam = getattr(rec, "family", None)
    if pt is None or not hasattr(fam, "expert_flops"):
        return None
    work = {p: w for p, w in work_per_execution(rec).items()
            if pt.executions.get(p)}
    if not work:
        return None
    cfg = rec.config
    n = len(work)
    flops = sum(fam.expert_flops(cfg, r) for r, _ in work.values()) / n
    nbytes = sum(fam.expert_bytes(cfg, r, h) for r, h in work.values()) / n
    return roofline_share(pt, ("moe.experts",), flops, nbytes, rec.peaks)
