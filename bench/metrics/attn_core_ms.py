"""Device ms per execution of a step program (the mean over the mixed
and the decode step) under ``attn.core``: scores and values, or the
Pallas kernel; self time."""
from bench.program_trace import ATTN_CORE, scope_ms


def read(rec):
    return scope_ms(getattr(rec, "program_trace", None), ATTN_CORE)
