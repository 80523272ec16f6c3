"""Mean occupied slots per dispatch over max_batch, in %."""
from bench.stats import slot_occupancy


def read(rec):
    occ = slot_occupancy(rec)
    return None if occ is None else 100.0 * occ
