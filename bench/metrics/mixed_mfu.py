"""Model FLOPs of the mixed steps over their device time x bf16 peak, in %."""
from bench.readers import MIXED, mfu


def read(rec):
    return mfu(rec, {"mixed": MIXED})
