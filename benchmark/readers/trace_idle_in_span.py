"""Seconds in which the chip was idle while the program was inside a
span named ``params["span"]``, per unit of ``params["unit"]`` done in
the traced slice, meaned over the chips. A gap that straddles the span's
edge counts for the part inside. Pointed at the span in which the host
WAITS for an asynchronous upload (not the one that starts it, which
ends when the host is released), it is the chip's wait for the bytes:
it falls when the upload overlaps compute, whatever the span's own
seconds do."""

from benchmark.readers import _xplane_program as xp


def read(params, obs):
    t = xp.this_run(obs)
    w = t and xp.window(t)
    units = (obs.get("traced_units") or {}).get(params["unit"])
    if not w or not units:
        return None
    cover = xp.union((s, e) for n, s, e in xp.program_spans(t)
                     if n == params["span"])
    idle = xp.idle_by_chip(t, *w)
    if not cover or not idle:
        return None
    inside = sum(xp.overlap(gaps, cover) for gaps in idle.values())
    return inside / len(idle) / 1e9 / units
