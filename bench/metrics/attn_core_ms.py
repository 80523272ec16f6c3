"""Device ms per step-program execution under ``attn.core``: scores and
values, or the Pallas kernel; self time."""
from bench.program_trace import ATTN_CORE, scope_ms


def read(rec):
    return scope_ms(getattr(rec, "program_trace", None), ATTN_CORE)
