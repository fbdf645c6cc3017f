"""Bytes a second a chip's links carry in an all-reduce: ``2 (p - 1) / p``
of the bytes a device hands it a step (a ring's traffic; the counter
``params["bytes"]`` over the window's ``params["unit"]``) over the device
seconds a step of the phase ``params["phase"]`` of the programs
``params["programs"]`` (``trace_phase_device_time``, a chip's mean). ``p``
is the counter ``params["workers"]`` over the window's ``params["per"]``
(``trainer.mesh_devices`` a fit). A rate, not a share of a peak:
``peaks.json`` has no interconnect peak.

None where the program counts neither (a program from before the
counters), on one worker, and where the phase holds no operation."""

from benchmark.readers import trace_phase_device_time


def read(params, obs):
    ms = trace_phase_device_time.read(params, obs)
    counters, units = obs["counters"], obs["units"]
    sent, steps = counters.get(params["bytes"]), units.get(params["unit"])
    workers, fits = counters.get(params["workers"]), units.get(params["per"])
    if not ms or not sent or not steps or not workers or not fits:
        return None
    p = workers / fits
    if p <= 1:
        return None
    return 2.0 * (p - 1.0) / p * (sent / steps) / (ms / 1e3)
