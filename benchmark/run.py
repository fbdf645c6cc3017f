#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process; refuses (exit code 2, no result line) unless JAX's default
backend is a TPU whose ``device_kind`` is in ``peaks.json`` and the
device count is the cell's ``chips``. Sets up (data and model data from
``--seed``, the compile cache, a warm-up of this cell's shapes), measures
for ``--seconds``, checks the outputs against the NumPy float64
reference, and prints one JSON object as the last line of stdout:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, with
``--trace 1``, ``breakdown``. Everything else goes on earlier lines.

Everything that belongs to one cell, one configuration or one per-layer
metric is a file found by name (see README.md); this file knows none of
them. ``--rehearse`` (never what the driver runs) shrinks rows as the
cell's file says, accepts any backend, and reports no device number.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import contextlib
import gc
import importlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILED = "/jax/core/compile/backend_compile_duration"
CACHE_MISS = "/jax/compilation_cache/cache_misses"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root: str, workload: str) -> dict:
    """The cell's entry of ``BENCHMARK.json`` with the files it names:
    its own, its configuration's, and one per per-layer metric that
    lists the cell (or lists none, which means every cell reporting the
    metric's ``moves``)."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    home = os.path.join(root, bench["paths"][0])
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(has: {[w['name'] for w in bench['workloads']]})")
    cell = _read_json(os.path.join(home, "workloads", f"{workload}.json"))
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = _read_json(os.path.join(root, cfg_entry["file"]))

    def lists_cell(m):
        return "workloads" not in m or workload in m["workloads"]

    end_to_end = [m for m in bench["end_to_end"] if lists_cell(m)]
    reported = {m["name"] for m in end_to_end}
    per_layer = [
        {**m, **_read_json(os.path.join(home, "metrics", f"{m['name']}.json"))}
        for m in bench["per_layer"]
        if lists_cell(m) and ("workloads" in m or m["moves"] in reported)
    ]
    return {"entry": entry, "cell": cell, "config": config, "home": home,
            "run_seconds": bench["run_seconds"],
            "end_to_end": end_to_end, "per_layer": per_layer}


class Monitor:
    """Counts, through ``jax.monitoring``, the programs JAX lowers (a new
    shape inside the window shows here whether or not the persistent
    cache then has it), the back-end compilations and the persistent
    cache's hits and misses."""

    def __init__(self):
        import jax

        self.n = {LOWERED: 0, COMPILED: 0, CACHE_MISS: 0, CACHE_HIT: 0}
        jax.monitoring.register_event_listener(self._count)
        jax.monitoring.register_event_duration_secs_listener(self._count)

    def _count(self, name, *_, **__):
        if name in self.n:
            self.n[name] += 1

    def snapshot(self) -> dict:
        return dict(self.n)


class Context:
    """What a driver is given: the cell, its configuration, the seed, the
    window's length, and :meth:`unit`, which it wraps around every unit
    of timed work (a fit, a transform call). With ``--trace 1`` the
    profiler runs from the first unit to the end of the
    ``trace_units``-th, under the span ``bench:window``; each unit is a
    span ``bench:<name>`` inside it."""

    def __init__(self, spec, seed, seconds, trace, rehearse, out_dir):
        # A rehearsal's overrides (rows, never widths) replace the cell's
        # own keys, so drivers and readers see one set of sizes.
        self.cell = {**spec["cell"],
                     **(spec["cell"].get("rehearse", {}) if rehearse else {})}
        self.config = spec["config"]
        self.seed, self.seconds = seed, seconds
        self.units, self.traced_units = {}, {}
        self.trace_dir = os.path.join(out_dir, "trace", spec["entry"]["name"])
        self._want = int(self.cell["trace_units"]) if trace else 0
        self._window = None
        self._done = 0

    def size(self, key):
        """A parameter of the cell, else of its configuration."""
        return self.cell.get(key, self.config.get(key))

    @property
    def tracing(self) -> bool:
        return self._window is not None

    def _start(self):
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("bench:window")
        self._window.__enter__()

    def stop(self):
        if self._window is not None:
            import jax

            self._window.__exit__(None, None, None)
            self._window = None
            jax.profiler.stop_trace()

    @contextlib.contextmanager
    def unit(self, name: str, **units):
        import jax

        if self._want and self._done == 0 and self._window is None:
            self._start()
        traced = self.tracing
        span = (jax.profiler.TraceAnnotation(f"bench:{name}") if traced
                else contextlib.nullcontext())
        with span:
            yield
        for book in (self.units, self.traced_units) if traced else (self.units,):
            for k, v in units.items():
                book[k] = book.get(k, 0) + v
        self._done += 1
        if traced and self._done >= self._want:
            self.stop()


def _flat_counters(snapshot: dict) -> dict:
    return {f"{group}.{name}": float(v)
            for group, g in snapshot.items()
            for name, v in g.get("counters", {}).items()}


def _delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def _say(record: dict) -> None:
    print(json.dumps(record), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="builder's CPU rehearsal: fewer rows, any backend, "
                         "no device number; never what the driver runs")
    args = ap.parse_args(argv)

    spec = load_spec(ROOT, args.workload)
    seconds = args.seconds if args.seconds is not None else spec["run_seconds"]
    out_dir = os.path.join(spec["home"], "out")
    os.makedirs(out_dir, exist_ok=True)
    # libtpu's own log goes inside the checkout, not to /tmp/tpu_logs.
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(out_dir, "tpu_logs"))

    try:
        import flinkml_tpu  # noqa: F401 — the system under test
    except ImportError as e:
        print(f"benchmark: the program is not importable from {ROOT}: {e}; "
              "nothing was run", file=sys.stderr)
        return 3
    import jax

    program = importlib.import_module("benchmark.drivers.program")
    driver = importlib.import_module(f"benchmark.drivers.{spec['cell']['driver']}")

    peaks_table = _read_json(os.path.join(spec["home"], "peaks.json"))["devices"]
    devices = jax.devices()
    kind = devices[0].device_kind
    if not args.rehearse:
        problem = None
        if jax.default_backend() != "tpu":
            problem = f"jax.default_backend() is {jax.default_backend()!r}, not 'tpu'"
        elif kind not in peaks_table:
            problem = f"device_kind {kind!r} is not in peaks.json"
        elif len(devices) != spec["entry"]["chips"]:
            problem = (f"the cell asks for {spec['entry']['chips']} chip(s) "
                       f"and JAX sees {len(devices)}")
        if problem:
            print(f"benchmark: {problem}; nothing was run", file=sys.stderr)
            return 2
    cache_dir = program.enable_compile_cache()
    monitor = Monitor()
    _say({"phase": "start", "workload": args.workload, "seed": args.seed,
          "seconds": seconds, "trace": args.trace, "rehearse": args.rehearse,
          "compile_cache_dir": cache_dir, "jax": jax.__version__,
          "devices": [str(d) for d in devices]})

    ctx = Context(spec, args.seed, seconds, bool(args.trace), args.rehearse, out_dir)
    c_start = _flat_counters(program.counters())
    state = driver.setup(ctx)
    # Everything imported and built so far goes to the permanent
    # generation: a full collection inside the window then walks the
    # window's own few objects, not a million module-level ones.
    gc.collect()
    gc.freeze()
    m_setup = monitor.snapshot()
    c_setup = _flat_counters(program.counters())
    setup_s = time.perf_counter() - T_START
    _say({"phase": "setup", "setup_s": setup_s,
          "first_run_here": m_setup[CACHE_MISS] > 0, "jax_events": m_setup})

    result = driver.window(ctx, state)
    ctx.stop()
    m_window = _delta(monitor.snapshot(), m_setup)
    c_window = _delta(_flat_counters(program.counters()), c_setup)
    _say({"phase": "window", "wall_s": result["wall_s"], "work": result["work"],
          "units": ctx.units, "jax_events_in_window": m_window,
          "unit_walls_s": (result.get("unit_walls_s") or [])[:64]})

    checks = driver.check(ctx, state, result, c_window)
    checks.append({"what": "programs lowered or compiled inside the window",
                   "value": m_window[LOWERED] + m_window[COMPILED], "limit": 0})
    for c in checks:
        c["ok"] = bool(c["value"] is not None and c["value"] <= c["limit"])
        _say({"phase": "check", **c})
    correct = all(c["ok"] for c in checks) and result["failed"] == 0

    if args.rehearse:
        device = {"platform": "cpu-rehearsal", "kind": kind,
                  "count": len(devices), "memory_peak_bytes": 0}
    else:
        device = {"platform": devices[0].platform, "kind": kind,
                  "count": len(devices),
                  "memory_peak_bytes": max(
                      int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                      for d in devices)}

    metrics, breakdown = {}, None
    if not args.trace:
        rate = result["work"] / result["wall_s"]
        values = {spec["cell"]["rate_metric"]: rate, "setup_s": setup_s}
        for m in spec["end_to_end"]:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        from benchmark import trace as trace_mod

        reduced = None
        if not args.rehearse:
            reduced = trace_mod.reduce(
                trace_mod.load(trace_mod.find_xplane(ctx.trace_dir)))
            device["busy_s"] = reduced["busy_mean_s"]
            device["window_s"] = reduced["window_s"]
            worst = min(reduced["busy_s"].items(), key=lambda kv: kv[1])
            _say({"phase": "trace", "busy_s_by_chip": reduced["busy_s"],
                  "least_busy_chip": worst[0], "spans": reduced["spans"][:12]})
            breakdown = {"device_ops": reduced["ops"][:10],
                         "idle_gaps": reduced["idle_gaps"][:10]}
        obs = {"trace": reduced, "counters": c_window,
               "setup_counters": {
                   **_delta(c_setup, c_start),
                   **{f"jax.{k.rsplit('/', 1)[-1]}": float(v)
                      for k, v in m_setup.items()}},
               "units": ctx.units, "traced_units": ctx.traced_units,
               "cell": ctx.cell, "config": ctx.config,
               "peaks": peaks_table.get(kind)}
        for m in spec["per_layer"]:
            reader = importlib.import_module(f"benchmark.readers.{m['reader']}")
            value = reader.read(m.get("params", {}), obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    _say(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
