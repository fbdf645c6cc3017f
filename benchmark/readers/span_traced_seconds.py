"""A span's seconds per PROFILED unit, from the program's counters
alone: ``span.<params["span"]>.traced_<params["field"]>`` (what the
spans added while a profiler was recording; ``field`` is ``seconds`` or
``self_seconds``) over ``traced_units[params["den"]]``.
``span_plain_seconds``' other half: a cell whose one profiled unit fills
the window (``lr-criteo.fit``: stopping the profiler takes longer than
the window is) has no plain unit to read, and its split is the profiled
fit's. None where the program writes no ``traced_*`` fields or no unit
was profiled."""


def read(params, obs):
    total = obs["counters"].get(
        f"span.{params['span']}.traced_{params['field']}")
    den = (obs.get("traced_units") or {}).get(params["den"])
    if total is None or not den:
        return None
    return total / float(den)
