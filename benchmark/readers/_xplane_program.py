"""This run's own profile, with what ``trace.load`` leaves out: the
program's host spans (``flinkml:<name>``, from
``flinkml_tpu.utils.profiling.span``) beside the benchmark's ``bench:``
ones. Not a reader: ``trace_idle_outside_spans`` and
``trace_idle_in_span`` share it.

The harness hands readers the reduced trace (``obs["trace"]``), not the
directory, so :func:`this_run` looks for the file: the ``.xplane.pb``
under ``benchmark/out/trace/`` written since this process started. It
gives ``None`` in a rehearsal (``obs["trace"]`` is None there: a CPU
profile has no chip). A traced run on the chip has written exactly one;
any other count is an error, not a metric left out.

The plain-data form is ``trace.load``'s, so ``trace.device_ops``,
``trace.spans`` and ``trace._busy_runs`` work on it unchanged.

No per-scope device time is read here. On a v5e trace of JAX 0.9 the
``jax.named_scope`` path of an operation is the stat ``tf_op`` of the
event's METADATA (``jit(per_device)/while/body/dot_general:``), and
``jax.profiler.ProfileData`` hands out an event's own stats only
(``device_offset_ps``, ``device_duration_ps``, ``Time Scale
Multiplier``): PERF.md section 7.
"""

from __future__ import annotations

import functools
import glob
import os

from benchmark import trace

PROGRAM_PREFIX = "flinkml:"
OUT_TRACE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "out", "trace")


def load(path: str) -> dict:
    """``trace.load`` with the ``flinkml:`` spans kept."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            lines = [
                {"name": ln.name, "events": [
                    [trace.op_name(e.name), float(e.start_ns), float(e.duration_ns)]
                    for e in ln.events]}
                for ln in plane.lines if ln.name == trace.OP_LINE
            ]
        elif plane.name == trace.HOST_PLANE:
            lines = []
            for ln in plane.lines:
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in ln.events
                       if e.name.startswith((trace.SPAN_PREFIX, PROGRAM_PREFIX))]
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
                 OUT_TRACE, "*", "plugins", "profile", "*", "*.xplane.pb"))
             if os.path.getmtime(p) >= started]
    if len(paths) != 1:
        raise RuntimeError(
            f"expected this run's one .xplane.pb under {OUT_TRACE}, written "
            f"since the process started; found {sorted(paths)}")
    return load(paths[0])


def this_run(obs) -> dict | None:
    """The traced run's profile as plain data, loaded once a process;
    None where the harness reduced no trace (a rehearsal)."""
    if not obs.get("trace"):
        return None
    return _this_runs_file()


def window(t: dict):
    """``(start_ns, end_ns)`` of ``bench:window``, or None."""
    name = trace.WINDOW_SPAN[len(trace.SPAN_PREFIX):]
    return next(((s, e) for n, s, e in trace.spans(t) if n == name), None)


def program_spans(t: dict):
    """The program's spans as ``(name, start_ns, end_ns)``, prefix off,
    from every host thread."""
    out = []
    for plane in t["planes"]:
        if plane["name"] != trace.HOST_PLANE:
            continue
        for line in plane["lines"]:
            out += [(n[len(PROGRAM_PREFIX):], s, s + d)
                    for n, s, d in line["events"] if n.startswith(PROGRAM_PREFIX)]
    return sorted(out, key=lambda s: s[1])


def union(intervals):
    """Sorted, merged ``[(start, end), ...]`` (``trace._busy_runs``
    without the names)."""
    ops = sorted((None, s, e) for s, e in intervals)
    return [(s, e) for s, e, _ in
            trace._busy_runs(ops, float("-inf"), float("inf"))]


def idle_by_chip(t: dict, lo: float, hi: float) -> dict:
    """Per chip, the intervals of ``[lo, hi]`` in which no operation ran:
    the complement of ``trace._busy_runs``."""
    out = {}
    for chip, ops in trace.device_ops(t).items():
        runs = trace._busy_runs(ops, lo, hi)
        edges = [lo] + [e for _, e, _ in runs]
        nexts = [s for s, _, _ in runs] + [hi]
        out[chip] = [(a, b) for a, b in zip(edges, nexts) if b > a]
    return out


def overlap(intervals, cover) -> float:
    """Nanoseconds of ``intervals`` that lie inside the merged ``cover``."""
    return sum(trace.total(trace.clip(intervals, s, e)) for s, e in cover)
