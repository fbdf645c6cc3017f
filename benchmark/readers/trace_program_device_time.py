"""Device milliseconds the chip spent running the PROGRAMS named in
``params["programs"]`` (names as ``flinkml_tpu.utils.profiling.
named_program`` gave them; the profile's ``XLA Modules`` events,
``_xplane_modules``) inside ``bench:window``, per unit of
``params["unit"]`` done in the traced slice, meaned over the chips. A
run that straddles the window's edge counts for the part inside. Scoped
by what ran, not by what the host was inside of: the loop's time stays
the loop's wherever the staging writes run. None where the profile has
no ``XLA Modules`` line or no run of these programs (a rehearsal, a
program that does not name them)."""

from benchmark import trace
from benchmark.readers import _xplane_modules as xm
from benchmark.readers import _xplane_program as xp


def device_seconds_per_unit(params, obs):
    t = xm.this_run(obs)
    w = t and xp.window(t)
    units = (obs.get("traced_units") or {}).get(params["unit"])
    by_chip = xm.programs(t) if w else {}
    if not by_chip or not units:
        return None
    wanted = set(params["programs"])
    ran = sum(trace.total(trace.clip(
        [(s, e) for n, s, e in runs if n in wanted], *w))
        for runs in by_chip.values())
    return ran / len(by_chip) / 1e9 / units if ran > 0 else None


def read(params, obs):
    s = device_seconds_per_unit(params, obs)
    return None if s is None else 1000.0 * s
