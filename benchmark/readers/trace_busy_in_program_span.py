"""Device busy milliseconds while the PROGRAM was inside its own span
``params["span"]`` (a ``flinkml:`` span, not the benchmark's ``bench:``
unit), per unit of ``params["unit"]`` done in the traced slice, meaned
over the chips: ``knn.search_device_ms_per_call`` is the chip's busy time
inside ``knn.search`` over the calls and ``kmeans.round_device_ms`` the
same inside ``kmeans.loop`` over the rounds, so what the chip runs for
the unit outside the span (an upload's writes, a read-back) is not in
it. An operation that straddles the span's edge counts for the part inside.
None where the program has no such span (a rehearsal, an older program).
"""

from benchmark import trace
from benchmark.readers import _xplane_program as xp


def busy_seconds_per_unit(params, obs):
    t = xp.this_run(obs)
    w = t and xp.window(t)
    units = (obs.get("traced_units") or {}).get(params["unit"])
    if not w or not units:
        return None
    cover = xp.union((max(s, w[0]), min(e, w[1]))
                     for n, s, e in xp.program_spans(t)
                     if n == params["span"] and min(e, w[1]) > max(s, w[0]))
    idle = xp.idle_by_chip(t, *w)
    if not cover or not idle:
        return None
    busy = sum(trace.total(cover) - xp.overlap(gaps, cover)
               for gaps in idle.values())
    return busy / len(idle) / 1e9 / units if busy > 0 else None


def read(params, obs):
    s = busy_seconds_per_unit(params, obs)
    return None if s is None else 1000.0 * s
