"""This run's own profile once more, for what names the chip's
PROGRAMS: each device plane's ``XLA Modules`` line (one event per
executed program, named by the module the host compiled:
``jit_<name>(<fingerprint>)``; ``trace.load`` and
``_xplane_program.load`` keep the ``XLA Ops`` line only) beside the
benchmark's ``bench:`` spans. Not a reader: ``trace_program_device_time``
and ``roofline_of_program`` share it.

The program names its hot path's executables
(``flinkml_tpu.utils.profiling.named_program``,
docs/development/observability.md "Programs"); :func:`program_name`
takes a module's name back to that: ``jit_lr_sparse_loop(123)`` and
``jit_lr_sparse_loop.2`` are both ``lr_sparse_loop``.

The file is found as ``_xplane_program._this_runs_file`` finds it; the
plain-data form is ``trace.load``'s, so ``trace.spans`` and
``_xplane_program.window`` work on it unchanged.
"""

from __future__ import annotations

import functools
import glob
import os
import re

from benchmark import trace
from benchmark.readers import _xplane_program as xp

MODULE_LINE = "XLA Modules"
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)$")


def program_name(module: str) -> str:
    """A module's name without ``jit_`` and without a trailing
    ``(<digits>)`` or ``.<digits>``."""
    name = module.strip()
    while _SUFFIX.search(name):
        name = _SUFFIX.sub("", name)
    return name[len("jit_"):] if name.startswith("jit_") else name


def load(path: str) -> dict:
    """``trace.load`` keeping the device planes' ``XLA Modules`` line
    (names through :func:`program_name`) in place of ``XLA Ops``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            lines = [
                {"name": ln.name, "events": [
                    [program_name(e.name), float(e.start_ns), float(e.duration_ns)]
                    for e in ln.events]}
                for ln in plane.lines if ln.name == MODULE_LINE
            ]
        elif plane.name == trace.HOST_PLANE:
            lines = []
            for ln in plane.lines:
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in ln.events if e.name.startswith(trace.SPAN_PREFIX)]
                if evs:
                    lines.append({"name": ln.name, "events": evs})
        else:
            continue
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


@functools.lru_cache(maxsize=1)
def _this_runs_file():
    import psutil

    started = psutil.Process().create_time()
    paths = [p for p in glob.glob(os.path.join(
                 xp.OUT_TRACE, "*", "plugins", "profile", "*", "*.xplane.pb"))
             if os.path.getmtime(p) >= started]
    if len(paths) != 1:
        raise RuntimeError(
            f"expected this run's one .xplane.pb under {xp.OUT_TRACE}, written "
            f"since the process started; found {sorted(paths)}")
    return load(paths[0])


def this_run(obs) -> dict | None:
    """The traced run's programs as plain data, loaded once a process;
    None where the harness reduced no trace (a rehearsal)."""
    if not obs.get("trace"):
        return None
    return _this_runs_file()


def programs(t: dict) -> dict:
    """``{plane name: [(program name, start_ns, end_ns), ...]}`` per
    chip; a chip whose plane has no ``XLA Modules`` line is left out."""
    out = {}
    for plane in t["planes"]:
        lines = [ln for ln in plane["lines"] if ln["name"] == MODULE_LINE]
        if trace.DEVICE_PLANE.match(plane["name"]) and lines:
            out[plane["name"]] = sorted(
                ((n, s, s + d) for ln in lines for n, s, d in ln["events"]),
                key=lambda r: r[1])
    return out
