"""Device ms per execution of a step program (the mean over the mixed
and the decode step) under the weight matmuls' scopes (``attn.qkv``,
``attn.out``, ``ffn``, ``head``), self time."""
from bench.program_trace import MATMUL, scope_ms


def read(rec):
    return scope_ms(getattr(rec, "program_trace", None), MATMUL)
