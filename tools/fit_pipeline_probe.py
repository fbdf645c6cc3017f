"""Whole ``LogisticRegression().fit(Table)`` calls of a benchmark cell
with the placement's three behaviours side by side, and the plain (no
profiler) split of a fit's seconds (PR 33; PERF.md §5, §6).

The benchmark prints counters on traced runs only, and a traced sparse
fit pays seconds of ``hostdata.stage_wait`` a plain one does not. This
probe reads the spans' counters around plain fits instead, in ONE
process on the chip, through the benchmark's own set-up of the cell::

    python tools/fit_pipeline_probe.py [--rehearse] [lr-criteo.fit] [lr-a9a.fit]

(``--rehearse``: the cells' rehearsal sizes, any backend, no device number.)

Variants (``<behaviour>-<staging buffer sets>``), each the program's own
code with two functions of ``_linear_sgd`` swapped:

- ``pipe``: the program as it is: the loop follows the placement's rounds;
- ``reach``: rows no step reads are not sent, but the loop waits for the
  last round (``_steps_ready`` answers only once the reach is complete);
- ``whole``: every row sent, then one dispatch: the fit before PR 33.

Every fit's coefficients are compared with set-up's, bit for bit. JSON
lines on stdout and in ``chiprun_out/fit_pipeline_probe.jsonl``.
"""
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmark import run as bench_run  # noqa: E402
from benchmark.drivers import fit as dense  # noqa: E402
from benchmark.drivers import program  # noqa: E402
from flinkml_tpu.models import _linear_sgd  # noqa: E402
from flinkml_tpu.parallel import mesh as mesh_mod  # noqa: E402
from flinkml_tpu.utils import metrics  # noqa: E402

SPANS = ("fit", "hostdata.ingest", "hostdata.sparse_pack", "hostdata.shuffle",
         "hostdata.stage_wait", "mesh.shard_batch", "trainer.loop",
         "trainer.readback")
VARIANTS = ("pipe-2", "pipe-3", "reach-2", "whole-2")
STEPS_READY, REACH_ROWS = _linear_sgd._steps_ready, _linear_sgd._reach_rows
os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
LOG = open(os.path.join(ROOT, "chiprun_out", "fit_pipeline_probe.jsonl"), "a")


def say(**record):
    line = json.dumps(record)
    print(line, flush=True)
    LOG.write(line + "\n")
    LOG.flush()


def use(variant):
    kind, buffers = variant.split("-")
    mesh_mod._STAGE_BUFFERS = int(buffers)
    _linear_sgd._reach_rows, _linear_sgd._steps_ready = REACH_ROWS, STEPS_READY
    if kind != "pipe":
        def ready(n_local, local_bs, first, last, complete):
            reach = _linear_sgd._reach_rows(n_local, local_bs, first, last)
            return last if complete >= reach else first
        _linear_sgd._steps_ready = ready
    if kind == "whole":
        _linear_sgd._reach_rows = (
            lambda n_local, local_bs, first, last: n_local if last > first else 0)


def counted():
    spans = metrics.group("span").snapshot()["counters"]
    out = {k: spans.get(f"{k}.seconds", 0.0) for k in SPANS}
    for group in ("trainer", "hostdata.stage"):
        for k, v in metrics.group(group).snapshot()["counters"].items():
            out[f"{group}.{k}"] = v
    return out


def probe(workload, seed, rehearse, rounds=3):
    spec = bench_run.load_spec(ROOT, workload)
    ctx = bench_run.Context(spec, seed, 10.0, False, rehearse,
                            os.path.join(ROOT, "benchmark", "out"))
    driver = importlib.import_module(f"benchmark.drivers.{spec['cell']['driver']}")
    use("pipe-2")
    t0 = time.perf_counter()
    state = driver.setup(ctx)
    say(phase="setup", workload=workload, seconds=time.perf_counter() - t0)
    for i in range(rounds):
        for variant in VARIANTS if i % 2 == 0 else VARIANTS[::-1]:
            use(variant)
            before, t0 = counted(), time.perf_counter()
            coef = dense._fit(ctx, state.table, state.batch, state.max_iter)
            wall, after = time.perf_counter() - t0, counted()
            say(phase="fit", workload=workload, variant=variant, wall_s=wall,
                equal=coef.tobytes() == state.coefs[0].tobytes(),
                added={k: round(after[k] - before.get(k, 0.0), 4) for k in after},
                peak_bytes=int((jax.devices()[0].memory_stats() or {}).get(
                    "peak_bytes_in_use", 0)))
    use("pipe-2")


if __name__ == "__main__":
    say(phase="start", backend=jax.default_backend(),
        devices=[str(d) for d in jax.devices()],
        compile_cache=program.enable_compile_cache())
    cells = [a for a in sys.argv[1:] if a != "--rehearse"]
    for n, cell in enumerate(cells or ["lr-criteo.fit", "lr-a9a.fit"]):
        probe(cell, 2147500033 + n, "--rehearse" in sys.argv)
    say(phase="done")
