"""90th percentile of time to first token, from each request's due time."""
from bench.stats import percentile, ttfts


def read(rec):
    return percentile(ttfts(rec), 90)
