"""Seconds in which the chip was idle while ``params["span"]`` was the
INNERMOST open span of the program, per unit of ``params["unit"]`` done
in the traced slice, meaned over the chips: the idle time inside the
cover of ``flinkml:<span>`` less the cover of every other ``flinkml:``
span that lies inside one of its intervals. ``trace_idle_in_span`` is
inclusive (since the loop holds the staging rounds, all idle time under
``trainer.loop`` is nearly all of a fit's); this one is exclusive, so
the entries of one fit's spans add up to the idle time under ``fit``.
Of the TRACED units: read beside ``tracing.traced_unit_excess_s``."""

from benchmark.readers import _xplane_program as xp


def read(params, obs):
    t = xp.this_run(obs)
    w = t and xp.window(t)
    units = (obs.get("traced_units") or {}).get(params["unit"])
    if not w or not units:
        return None
    spans = xp.program_spans(t)
    own = [(s, e) for n, s, e in spans if n == params["span"]]
    idle = xp.idle_by_chip(t, *w)
    if not own or not idle:
        return None
    under = xp.union((s, e) for n, s, e in spans if n != params["span"]
                     and any(a <= s and e <= b for a, b in own))
    cover = xp.union(own)
    inside = sum(xp.overlap(gaps, cover) - xp.overlap(gaps, under)
                 for gaps in idle.values())
    return inside / len(idle) / 1e9 / units
