"""Share of the attended key positions that latent attention's kernel
reads, in %: 100 x live keys / keys over the window's ``dispatch``
events (every program).  ``keys`` counts each occupied slot's whole
view; ``live_keys`` the positions in the key blocks up to its last
lane, an upper bound.  None where the events carry no such fields."""


def read(rec):
    keys = live = 0
    for kind, _, _, t, data in rec.events:
        if kind == "dispatch" and "keys" in data and rec.t0 <= t <= rec.t1:
            keys += data["keys"]
            live += data["live_keys"]
    return 100.0 * live / keys if keys else None
