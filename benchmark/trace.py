"""From a profiler trace (``*.xplane.pb``) to the numbers the per-layer
readers use: per-chip busy seconds inside the traced window, seconds per
device operation, the benchmark's own host spans, and the idle gaps by
what the host was doing.

A trace is handled as plain data, ``{"planes": [{"name", "lines":
[{"name", "events": [[name, start_ns, duration_ns], ...]}]}]}``, which
is what :func:`load` makes of the file and what the recorded trace under
``tests/`` holds, so the reduction is checked without a profiler.

What counts as what, for a TPU trace of JAX 0.9 (read off a v5e trace,
see README.md):

- a chip is a plane named ``/device:TPU:<n>``;
- its operations are the events of the line ``XLA Ops`` (one event per
  executed HLO operation; a ``while`` loop's body operations appear, the
  loop itself too, which is why busy time is a union of intervals and
  never a sum);
- the benchmark's spans are ``TraceAnnotation`` events whose names start
  with ``bench:``, on any line of the plane ``/host:CPU``;
- the window is the span ``bench:window``.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
# A span's lead ends at its first operation at least this share of its
# longest one: a zero-fill of the coefficient is not what the host was
# preparing for, the training loop or a pass over the table is.
LEAD_FLOOR = 0.1


def op_name(hlo: str) -> str:
    """``%fusion.4 = f32[...] fusion(...)`` -> ``fusion.4``: the
    instruction's name without its shapes and operands."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """Read an ``.xplane.pb`` into plain data. Only the device planes'
    operation line and the host plane's ``bench:`` spans are kept, so a
    long trace stays small in memory."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = [
                {"name": ln.name, "events": [
                    [op_name(e.name), float(e.start_ns), float(e.duration_ns)]
                    for e in ln.events]}
                for ln in plane.lines if ln.name == OP_LINE
            ]
        elif plane.name == HOST_PLANE:
            lines = []
            for ln in plane.lines:
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in ln.events if e.name.startswith(SPAN_PREFIX)]
                if evs:
                    lines.append({"name": ln.name, "events": evs})
        else:
            continue
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def spans(trace: dict):
    """The benchmark's host spans as ``(name, start_ns, end_ns)`` with
    the ``bench:`` prefix taken off, in order of start."""
    out = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    out.append((name[len(SPAN_PREFIX):], start, start + dur))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def device_ops(trace: dict):
    """``{plane name: [(op name, start_ns, end_ns), ...]}`` per chip, in
    order of start."""
    out = {}
    for plane in trace["planes"]:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        ops = []
        for line in plane["lines"]:
            if line["name"] == OP_LINE:
                ops += [(n, s, s + d) for n, s, d in line["events"]]
        out[plane["name"]] = sorted(ops, key=lambda o: o[1])
    return out


def _busy_runs(ops, lo, hi):
    """The union of the operations' intervals inside ``[lo, hi]`` as
    ``(start, end, name of the operation that ends the run)``; ``ops``
    are in order of start."""
    runs = []
    for name, s, e in ops:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if runs and s <= runs[-1][1]:
            if e > runs[-1][1]:
                runs[-1][1], runs[-1][2] = e, name
        else:
            runs.append([s, e, name])
    return runs


def _innermost(span_list, t):
    """Name of the shortest span that covers time ``t`` (the window
    itself only if nothing else does)."""
    best = None
    for name, s, e in span_list:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside-any-span"


def reduce(trace: dict) -> dict:
    """Everything the readers need, in seconds.

    ``window_s``: length of ``bench:window``. ``busy_s``: per chip, the
    union of operation intervals inside the window; ``busy_mean_s`` their
    mean. ``ops``: ``[[name, seconds], ...]`` summed over chips and
    divided by their number, longest first. ``spans``: every other
    ``bench:`` span as ``{"name", "start_s", "end_s", "busy_s" (mean over
    chips of operation time inside it), "lead_s" (from its start to the
    start of the first operation inside it that lasts at least
    ``LEAD_FLOOR`` of the span's longest — the training loop of a fit,
    the first pass over the table of a transform call — on the earliest
    chip; None if no operation starts inside it)}`` with times relative to the window's start.
    ``idle_gaps``: the idle time of each chip inside the window, by ``<innermost span> after <operation before the gap>``,
    meaned over chips, longest first."""
    all_spans = spans(trace)
    windows = [s for s in all_spans if s[0] == WINDOW_SPAN[len(SPAN_PREFIX):]]
    if not windows:
        raise ValueError("the trace holds no bench:window span")
    _, w0, w1 = windows[0]
    inner = [s for s in all_spans
             if s[0] != windows[0][0] and s[1] >= w0 and s[2] <= w1 + 1]
    ops_by_chip = device_ops(trace)
    if not ops_by_chip:
        raise ValueError("the trace holds no device plane")
    n_chips = len(ops_by_chip)
    busy, op_seconds, gaps = {}, {}, {}
    span_busy = [0.0] * len(inner)
    span_lead = [None] * len(inner)
    for chip, ops in ops_by_chip.items():
        runs = _busy_runs(ops, w0, w1)
        merged = [(s, e) for s, e, _ in runs]
        busy[chip] = total(merged) / 1e9
        for name, s, e in ops:
            cs, ce = max(s, w0), min(e, w1)
            if ce > cs:
                op_seconds[name] = op_seconds.get(name, 0.0) + (ce - cs) / 1e9
        # Idle gaps: before, between and after the busy runs, each named
        # by the innermost span at its middle and the operation it follows.
        edges = [(w0, "window-start")] + [(e, last) for _, e, last in runs]
        nexts = [s for s, _, _ in runs] + [w1]
        for (g0, prev), g1 in zip(edges, nexts):
            if g1 > g0:
                key = f"{_innermost(inner, (g0 + g1) / 2)} after {prev}"
                gaps[key] = gaps.get(key, 0.0) + (g1 - g0) / 1e9
        for j, (_, s0, s1) in enumerate(inner):
            span_busy[j] += total(clip(merged, s0, s1)) / 1e9
            inside = [o for o in ops if s0 <= o[1] <= s1]
            if inside:
                floor = LEAD_FLOOR * max(o[2] - o[1] for o in inside)
                first = next(o for o in inside if o[2] - o[1] >= floor)
                lead = (first[1] - s0) / 1e9
                if span_lead[j] is None or lead < span_lead[j]:
                    span_lead[j] = lead

    def rank(d, least=0.0):
        return sorted(([k, v / n_chips] for k, v in d.items()
                       if v / n_chips > least), key=lambda kv: -kv[1])

    return {
        "window_s": (w1 - w0) / 1e9,
        "chips": n_chips,
        "busy_s": busy,
        "busy_mean_s": sum(busy.values()) / n_chips,
        "ops": rank(op_seconds),
        "idle_gaps": rank(gaps, least=1e-6),
        "spans": [
            {"name": name, "start_s": (s0 - w0) / 1e9, "end_s": (s1 - w0) / 1e9,
             "busy_s": span_busy[j] / n_chips, "lead_s": span_lead[j]}
            for j, (name, s0, s1) in enumerate(inner)
        ],
    }
