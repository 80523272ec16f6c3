"""Device-idle ms per engine step while the host was inside
``ServingEngine.step``: the round trip between two step programs (the
harvest's read-back, admission, reservation and dispatch)."""
from bench.program_trace import host_idle_ms


def read(rec):
    return host_idle_ms(getattr(rec, "program_trace", None))
