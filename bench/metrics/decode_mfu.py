"""Model FLOPs of the decode steps over their device time x bf16 peak, in %."""
from bench.readers import DECODE, mfu


def read(rec):
    return mfu(rec, {"decode": DECODE})
