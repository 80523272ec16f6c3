"""Device ms per execution of the one-lane decode step."""
from bench.readers import DECODE, step_ms


def read(rec):
    return step_ms(rec, DECODE)
