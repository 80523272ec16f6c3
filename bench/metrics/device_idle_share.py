"""Device idle share, in %: over the window in a backlog, and in an open
loop only while a request is in the engine (the host's sleeps between
arrivals left out)."""
from bench.readers import idle_share


def read(rec):
    return idle_share(rec, occupied_only=rec.loop == "rate")
