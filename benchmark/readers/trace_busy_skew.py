"""100 * (busiest chip's busy seconds - least busy chip's) / (the chips'
mean), inside ``bench:window`` (``trace.reduce``'s ``busy_s`` by chip): in
a lockstep program a chip that lags is the others' wait in the
collective. None on one chip (no skew to read) and in a rehearsal."""


def read(params, obs):
    t = obs.get("trace")
    busy = list((t or {}).get("busy_s", {}).values())
    if len(busy) < 2 or sum(busy) <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / (sum(busy) / len(busy))
