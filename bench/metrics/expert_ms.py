"""Device ms per execution of a step program (the mean over the mixed
and the decode step) under the MoE scopes (``moe.route``,
``moe.dispatch``, ``moe.experts``, ``moe.combine``, ``moe.shared``),
self time.  None where no op ran under them (a program without the
scopes, or without experts)."""
from bench.program_trace import scope_ms

MOE = ("moe.route", "moe.dispatch", "moe.experts", "moe.combine",
       "moe.shared")


def read(rec):
    pt = getattr(rec, "program_trace", None)
    if pt is None or not any(sc in MOE for _, sc in pt.self_ns):
        return None
    return scope_ms(pt, MOE)
