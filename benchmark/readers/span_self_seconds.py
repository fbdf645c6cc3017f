"""A span's self time per unit, from the program's counters alone:
``span.<params["span"]>.seconds`` less the seconds of the spans
``params["children"]`` (those that run inside it in this cell), over
``units[params["den"]]``, all over the whole window. What no phase of
the program explains."""


def read(params, obs):
    counters = obs["counters"]
    own = counters.get(f"span.{params['span']}.seconds")
    den = obs["units"].get(params["den"])
    if own is None or not den:
        return None
    inside = sum(counters.get(f"span.{c}.seconds", 0.0)
                 for c in params["children"])
    return (own - inside) / float(den)
