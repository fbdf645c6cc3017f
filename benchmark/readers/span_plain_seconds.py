"""A span's seconds per UNPROFILED unit, from the program's counters
alone: (``span.<params["span"]>.<params["field"]>`` less its
``traced_<field>``, what the spans added while a profiler was recording)
over (``units[params["den"]]`` less ``traced_units[params["den"]]``),
both over the whole window. ``field`` is ``seconds`` or ``self_seconds``
(the span's seconds less its children's, which the program records:
``flinkml_tpu.utils.profiling.span``). A traced run profiles its first
``trace_units`` units and runs the rest plain; the older
``counter_ratio`` metrics blend the two. None where the program writes
no ``self_seconds`` (one from before the span tree) or no plain unit
ran."""


def plain(counters, span, field):
    """The field's sum over the spans no profiler saw, or None on a
    program without the span tree's fields."""
    if f"span.{span}.self_seconds" not in counters:
        return None
    return (counters[f"span.{span}.{field}"]
            - counters.get(f"span.{span}.traced_{field}", 0.0))


def read(params, obs):
    total = plain(obs["counters"], params["span"], params["field"])
    den = (obs["units"].get(params["den"], 0)
           - (obs.get("traced_units") or {}).get(params["den"], 0))
    if total is None or den <= 0:
        return None
    return total / float(den)
