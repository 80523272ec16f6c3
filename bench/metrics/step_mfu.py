"""Model FLOPs of every step over the step programs' device time x bf16 peak, in %."""
from bench.readers import DECODE, MIXED, mfu


def read(rec):
    return mfu(rec, {"mixed": MIXED, "decode": DECODE})
