"""Share of the mixed step's query lanes that serve no token, in %:
1 - live lanes / lanes over the window's mixed ``dispatch`` events."""


def read(rec):
    lanes = live = 0
    for kind, _, _, t, data in rec.events:
        if kind == "dispatch" and data["program"] == "mixed" \
                and rec.t0 <= t <= rec.t1:
            lanes += data["lanes"]
            live += data["live_lanes"]
    return 100.0 * (1.0 - live / lanes) if lanes else None
