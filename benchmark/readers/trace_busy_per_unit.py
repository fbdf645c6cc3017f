"""Device busy milliseconds inside the spans named ``params["span"]``,
per unit of ``params["unit"]`` done in the traced slice (``steps``,
``calls``): ``fusion.device_ms_per_call``."""


def busy_seconds_per_unit(params, obs):
    t = obs.get("trace")
    units = (obs.get("traced_units") or {}).get(params["unit"])
    if not t or not units:
        return None
    busy = sum(s["busy_s"] for s in t["spans"] if s["name"] == params["span"])
    return busy / units if busy > 0 else None


def read(params, obs):
    s = busy_seconds_per_unit(params, obs)
    return None if s is None else 1000.0 * s
