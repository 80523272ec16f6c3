"""Device-idle ms per engine step under the host's own phases:
``engine.admit``, ``engine.capacity``, ``engine.dispatch``, or
``engine.harvest`` outside its reads from the device."""
from bench.program_trace import host_idle_ms


def read(rec):
    return host_idle_ms(getattr(rec, "program_trace", None))
