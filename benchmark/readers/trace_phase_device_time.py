"""Device milliseconds the chip spent in the PHASE ``params["phase"]`` (a
name ``flinkml_tpu.utils.profiling.phase`` gave a part of a compiled
program) of the programs named in ``params["programs"]``, inside
``bench:window``, per unit of ``params["unit"]`` done in the traced
slice, meaned over the chips as ``trace_program_device_time`` means the
programs' whole runs. Exclusive (``_xplane_phases``): an instant is its
innermost operation's, so a program's phases and its unphased time add up
to that reader's number for it.

``"phase": null`` reads the time in NO phase (instants whose innermost
operation carries none, or at which none runs), and ``"unit": null`` a
share: percent of the programs' own device time in the window.

None where the profile is not a chip's (a rehearsal), where none of these
programs ran, where their operations carry no phase at all (a program
from before its phases), and where no operation of theirs carries this
one: a program that lost a name leaves its metric out of the line."""

from benchmark.readers import _xplane_phases as xph


def read(params, obs):
    found = xph.this_run(obs)
    if not found:
        return None
    ran = [found["programs"][p] for p in params["programs"]
           if p in found["programs"]]
    total = sum(r["ns"] for r in ran)
    named = [ns for r in ran for ns in r["phases"].values()]
    if total <= 0 or not named:
        return None
    if params["phase"] is None:
        ns = total - sum(named)
    else:
        mine = [r["phases"][params["phase"]] for r in ran
                if params["phase"] in r["phases"]]
        if not mine:
            return None
        ns = sum(mine)
    if params["unit"] is None:
        return 100.0 * ns / total
    units = (obs.get("traced_units") or {}).get(params["unit"])
    return ns / found["chips"] / 1e6 / units if units else None
