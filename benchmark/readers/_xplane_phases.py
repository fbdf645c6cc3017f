"""This run's own profile once more, for what ``jax.profiler.
ProfileData`` withholds: the PHASE of each device operation, the name the
program gave a part of a compiled program
(``flinkml_tpu.utils.profiling.phase``: ``jax.named_scope`` with the
prefix ``flinkml.``; docs/development/observability.md "Phases"). Not a
reader: ``trace_phase_device_time`` reads every phase metric off it.

On a v5e profile of JAX 0.9 (read off one, PR 49) an operation's
``op_name`` path, scopes and all
(``jit(w2v_sgns_loop.123)/while/body/closed_call/flinkml.w2v.sort/sort:``),
is the stat ``tf_op`` of its ``XEventMetadata``, beside the stat
``program_id`` of the module it belongs to; ``event_metadata`` and
``stat_metadata`` are maps at the top level of a plane. So :func:`names`
walks the file's protobuf wire format itself (no dependency, no
``ProfileData`` parse), descends ONLY into the device planes' two maps
and steps over every ``lines`` field by its length: a few thousand
entries however many events the profile holds. The operations' intervals
and the programs' runs are ``_xplane_program``'s and ``_xplane_modules``'
plain data, loaded once a process for the other readers already.

**Attribution is exclusive** (:func:`attribute`): inside a run of a
program (its ``XLA Modules`` event) each instant goes to the phase of the
innermost operation running, the latest started of those that cover it (a
``while`` or a ``body.N`` covers its children, which carry the loop's
scope in their own paths), and an instant at which no operation runs, or
one with no phase, to none. A program's phases and its unphased time so
add up to its device time, which is ``trace_program_device_time``'s.

The file is found as ``_xplane_program._this_runs_file`` finds it.
"""

from __future__ import annotations

import bisect
import functools
import glob
import itertools
import os
import re

from benchmark import trace
from benchmark.readers import _xplane_modules as xm
from benchmark.readers import _xplane_program as xp

#: ``flinkml_tpu.utils.profiling.PHASE_PREFIX`` (the benchmark's files
#: also run over a program that has none).
PHASE_PREFIX = "flinkml."
_RUN_OF = re.compile(r"\((\d+)\)$")

# Field numbers of tsl/profiler/protobuf/xplane.proto.
_PLANES = 1                                           # XSpace
_NAME, _EVENT_METADATA, _STAT_METADATA = 2, 4, 5      # XPlane (3: its lines)
_VALUE = 2                                            # a map's entry
_ID, _STATS = 1, 5                                    # X{Event,Stat}Metadata
_METADATA_ID, _UINT64, _INT64, _STR, _REF = 1, 3, 4, 5, 7       # XStat


def _varint(buf, at: int):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, at
        shift += 7


def fields(buf, lo: int, hi: int):
    """The fields of the message ``buf[lo:hi]`` as ``(number, value,
    end)``: a varint's ``(number, its value, None)``, a length-delimited
    field's ``(number, payload's first byte, payload's end)``, which is
    stepped over without a byte of it read."""
    at = lo
    while at < hi:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
            yield number, value, None
        elif wire == 2:
            size, at = _varint(buf, at)
            yield number, at, at + size
            at += size
        elif wire in (1, 5):
            at += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} at byte {at} of the profile")
    if at != hi:
        raise ValueError(f"a message of the profile ends at byte {at}, not {hi}")


def _text(buf, lo: int, hi: int) -> str:
    return bytes(buf[lo:hi]).decode("utf-8", "replace")


def _entries(buf, maps):
    """The value messages of a map's entries, ``(start, end)`` each."""
    for lo, hi in maps:
        for number, a, b in fields(buf, lo, hi):
            if number == _VALUE and b is not None:
                yield a, b


def phase_of(path: str):
    """The phase in an ``op_name`` path, None where it has none."""
    for part in path.split("/"):
        if part.startswith(PHASE_PREFIX):
            return part[len(PHASE_PREFIX):].rstrip(":")
    return None


def names(buf) -> dict:
    """``{program name: [{operation name as trace.op_name makes it: phase
    or None}, ...]}`` off the device planes of the ``XSpace`` ``buf``: a
    map for each compiled module of that name (``_xplane_modules.
    program_name``: a program compiled for two plans is two)."""
    by_id, name_of = {}, {}
    for number, lo, hi in fields(buf, 0, len(buf)):
        if number != _PLANES or hi is None:
            continue
        plane, events, stats = "", [], []
        for n, a, b in fields(buf, lo, hi):
            if n == _NAME:
                plane = _text(buf, a, b)
            elif n == _EVENT_METADATA:
                events.append((a, b))
            elif n == _STAT_METADATA:
                stats.append((a, b))
        if not trace.DEVICE_PLANE.match(plane):
            continue
        stat_name = {}
        for lo2, hi2 in _entries(buf, stats):
            ident = text = None
            for n, a, b in fields(buf, lo2, hi2):
                if n == _ID:
                    ident = a
                elif n == _NAME:
                    text = _text(buf, a, b)
            stat_name[ident] = text
        for lo2, hi2 in _entries(buf, events):
            name, path, program = "", None, None
            for n, a, b in fields(buf, lo2, hi2):
                if n == _NAME:
                    name = _text(buf, a, b)
                elif n == _STATS:
                    which = value = None
                    for m, c, d in fields(buf, a, b):
                        if m == _METADATA_ID:
                            which = stat_name.get(c)
                        elif m == _STR:
                            value = _text(buf, c, d)
                        elif m == _REF:
                            value = stat_name.get(c)
                        elif m in (_UINT64, _INT64):
                            value = c
                    if which == "tf_op":
                        path = value
                    elif which == "program_id":
                        program = value
            if program is not None:
                by_id.setdefault(program, {})[trace.op_name(name)] = (
                    phase_of(path) if path else None)
            else:
                run_of = _RUN_OF.search(name)
                if run_of:
                    name_of[int(run_of.group(1))] = xm.program_name(name)
    out = {}
    for program, ops in by_id.items():
        if program in name_of:
            out.setdefault(name_of[program], []).append(ops)
    return out


def _of_run(variants, seen):
    """The module whose operations a run's are: of a program compiled
    more than once, the one that differs least from the names ``seen``."""
    if len(variants) == 1:
        return variants[0]
    return min(variants, key=lambda ops: len(seen ^ ops.keys()))


def _exclusive(ops, lo: float, hi: float, phases: dict, out: dict) -> None:
    """Adds to ``out[phase]`` the nanoseconds of ``[lo, hi]`` whose
    innermost operation (``ops``: ``(name, start, end)`` by start, the
    longer first) is in ``phase``."""
    running, cursor = [], lo        # (end, phase), the latest started last
    for name, start, end in itertools.chain(ops, [(None, hi, hi)]):
        start, end = max(start, lo), min(end, hi)
        while cursor < start and running:
            until, phase = running[-1]
            if until <= cursor:
                running.pop()
                continue
            upto = min(until, start)
            if phase is not None:
                out[phase] = out.get(phase, 0.0) + upto - cursor
            cursor = upto
        cursor = max(cursor, start)
        if end > start:
            running.append((end, phases.get(name)))


def attribute(ops_by_chip: dict, runs_by_chip: dict, window, phases: dict) -> dict:
    """``{program name: {"ns": its runs' nanoseconds inside ``window``,
    "phases": {phase: nanoseconds}}}``, summed over the chips, for the
    programs of ``phases`` (:func:`names`); ``ops_by_chip`` as
    ``trace.device_ops`` and ``runs_by_chip`` as ``_xplane_modules.
    programs`` give them."""
    lo, hi = window
    out = {}
    for chip, runs in runs_by_chip.items():
        ops = sorted(ops_by_chip.get(chip, ()), key=lambda o: (o[1], -o[2]))
        starts = [o[1] for o in ops]
        for program, start, end in runs:
            a, b = max(start, lo), min(end, hi)
            if program not in phases or b <= a:
                continue
            inside = ops[bisect.bisect_left(starts, start):
                         bisect.bisect_left(starts, end)]
            entry = out.setdefault(program, {"ns": 0.0, "phases": {}})
            entry["ns"] += b - a
            of_op = _of_run(phases[program], {o[0] for o in inside})
            for phase in set(of_op.values()) - {None}:
                entry["phases"].setdefault(phase, 0.0)
            _exclusive(inside, a, b, of_op, entry["phases"])
    return out


def _this_runs_path() -> str:
    import psutil

    started = psutil.Process().create_time()
    paths = [p for p in glob.glob(os.path.join(
                 xp.OUT_TRACE, "*", "plugins", "profile", "*", "*.xplane.pb"))
             if os.path.getmtime(p) >= started]
    if len(paths) != 1:
        raise RuntimeError(
            f"expected this run's one .xplane.pb under {xp.OUT_TRACE}, written "
            f"since the process started; found {sorted(paths)}")
    return paths[0]


@functools.lru_cache(maxsize=1)
def _this_runs_phases():
    with open(_this_runs_path(), "rb") as f:
        phases = names(memoryview(f.read()))
    ops, runs = xp._this_runs_file(), xm._this_runs_file()
    window = xp.window(runs)
    by_chip = xm.programs(runs)
    if window is None or not by_chip:
        return None
    return {"chips": len(by_chip),
            "programs": attribute(trace.device_ops(ops), by_chip, window, phases)}


def this_run(obs) -> dict | None:
    """``{"chips": chips with an ``XLA Modules`` line, "programs":
    :func:`attribute`'s}`` of the traced run, made once a process; None
    where the harness reduced no trace (a rehearsal) or the profile has
    no window or no ``XLA Modules`` line."""
    if not obs.get("trace"):
        return None
    return _this_runs_phases()
