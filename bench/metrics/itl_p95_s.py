"""95th percentile of every gap between consecutive output tokens."""
from bench.stats import itl_samples, percentile


def read(rec):
    return percentile(itl_samples(rec), 95)
