"""Device ms per execution of a step program (the mean over the mixed
and the decode step) under the paged KV pool's scopes
(``attn.kv_write``, ``attn.kv_gather``), self time."""
from bench.program_trace import KV_POOL, scope_ms


def read(rec):
    return scope_ms(getattr(rec, "program_trace", None), KV_POOL)
