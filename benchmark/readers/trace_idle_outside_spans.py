"""100 * (chip idle time inside ``bench:window`` that no span of the
program covers) / (chip idle time inside the window), meaned over the
chips: what the program's instrumentation does not explain. The spans
named in ``params["roots"]`` (``fit``, ``transform``: they cover
everything) do not count as cover."""

from benchmark.readers import _xplane_program as xp


def read(params, obs):
    t = xp.this_run(obs)
    w = t and xp.window(t)
    if not w:
        return None
    roots = set(params.get("roots", ()))
    spans = [s for s in xp.program_spans(t) if s[0] not in roots]
    if not spans:
        return None
    cover = xp.union((s, e) for _, s, e in spans)
    shares = []
    for gaps in xp.idle_by_chip(t, *w).values():
        idle = sum(b - a for a, b in gaps)
        if idle > 0:
            shares.append(1.0 - xp.overlap(gaps, cover) / idle)
    return 100.0 * sum(shares) / len(shares) if shares else None
