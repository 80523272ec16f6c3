"""Output tokens harvested inside the window per second of it."""
from bench.stats import output_tokens


def read(rec):
    return output_tokens(rec) / (rec.t1 - rec.t0)
