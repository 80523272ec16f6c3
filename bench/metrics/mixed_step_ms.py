"""Device ms per execution of the fused mixed (chunked prefill) step."""
from bench.readers import MIXED, step_ms


def read(rec):
    return step_ms(rec, MIXED)
