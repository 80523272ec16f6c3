"""Device ms per execution of a step program (the mean over the mixed
and the decode step) in ops under no named scope, self time."""
from bench.program_trace import unscoped_ms


def read(rec):
    return unscoped_ms(getattr(rec, "program_trace", None))
