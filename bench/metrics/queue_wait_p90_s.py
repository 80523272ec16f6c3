"""90th percentile of submit -> admit in the scheduler."""
from bench.stats import percentile, queue_waits


def read(rec):
    return percentile(queue_waits(rec), 90)
