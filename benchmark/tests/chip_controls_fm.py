#!/usr/bin/env python3
"""The controls of ``fm-criteo.fit`` at the cell's own size, for a builder
to run ON THE CHIP (``python benchmark/tests/chip_controls_fm.py --seeds
1``), beside ``chip_controls_kmeans.py``. For each seed, from
``drivers/fm.py``'s own set-up, comparison and verdicts (the lines that
decide ``correct``):

- *sound*: the program as it is (the lookup's and the accumulation's
  products at ``Precision.HIGHEST``): set-up's fit of each of the sweep's
  pairs against the float64 reference's replay; ``correct`` has to come
  out true;
- *control*: the same rows on the chip, the same start, the program's
  own whole-run trainer with those products in ONE bfloat16 pass (its
  static ``precision`` at ``Precision.DEFAULT``: what a program computing
  in the nearest precision below float32 would do): the same gap, which
  has to come out well above its limit, and ``correct`` false;
- *the lookup alone*, on the chip, at the cell's longest block: ``ops.
  sparse.block_lookup`` with a payload of 17 against the gathered rows,
  bit for bit at ``HIGHEST`` (no CPU run can show it: there every
  precision is float32) and not at ``DEFAULT``.

A seed makes its own 5.4 GB table, and the chip's host hands freed pages
back late (PR 32): run one process a seed (``--seeds 1 --first-seed
<n>``, in a loop).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def lookup_alone(precision, length=26_624, width=17, rows=65_536, seed=0):
    """Looked-up floats that differ from the gathered ones, of ``2 * rows
    * width``: two slots' cells in blocks of ``length`` columns."""
    import jax

    from flinkml_tpu.ops import sparse

    rng = np.random.default_rng(seed)
    blocks = rng.standard_normal((2, length, width)).astype(np.float32)
    local = rng.integers(0, length, (2, rows)).astype(np.int32)
    got = np.asarray(jax.jit(
        lambda b, i: sparse.block_lookup(b, i, precision))(blocks, local))
    want = np.stack([blocks[s][local[s]] for s in range(2)])
    return int(np.count_nonzero(got != want)), float(np.abs(got - want).max())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=2_147_496_000)
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's rehearsal rows (a CPU rehearsal of this script)")
    args = ap.parse_args()

    from benchmark import run
    from benchmark.drivers import fm as driver, program
    import jax

    program.enable_compile_cache()
    print(json.dumps({"devices": [str(d) for d in jax.devices()]}), flush=True)
    spec = run.load_spec(ROOT, "fm-criteo.fit")
    one_pass = jax.lax.Precision.DEFAULT
    exact = lookup_alone(jax.lax.Precision.HIGHEST)
    rounded = lookup_alone(one_pass)
    print(json.dumps({"lookup_floats_off_at_highest": exact[0],
                      "lookup_floats_off_in_one_pass": rounded[0],
                      "lookup_widest_gap_in_one_pass": rounded[1]}), flush=True)

    def verdict(ctx, s, which, ref, fit):
        # The cell's own checks of one fit outside any window (so the
        # window's counters are given as what they have to be).
        s.timed = [(which, s.first[which])]
        cmp = driver.compare(ref, fit)
        cells = float(s.rows * s.nnz)
        checks = driver.verdicts(ctx, s, cmp, {
            "fm.table_h2d_bytes": 0.0, "fm.steps": float(s.max_iter),
            "fm.fits": 1.0, "fm.cells": cells, "fm.blocked_cells": cells,
            "table.csr_rows_materialized": 0.0})
        ok = lambda c: c["value"] is not None and c["value"] <= c["limit"]
        return {"correct": all(ok(c) for c in checks),
                "failed_checks": [c["what"][:50] for c in checks if not ok(c)],
                "gap": cmp["gap"], "gaps": cmp.get("gaps"),
                "rms_gap_in_rates": cmp["rms_gap_in_rates"],
                "worst_factor": cmp.get("worst_factor")}

    for seed in range(args.first_seed, args.first_seed + args.seeds):
        ctx = run.Context(spec, seed, 0.0, False, args.rehearse,
                          os.path.join(spec["home"], "out"))
        t0 = time.perf_counter()
        s = driver.setup(ctx)
        print(json.dumps({"seed": seed, "rows": s.rows,
                          "setup_s": time.perf_counter() - t0}), flush=True)
        for which, pair in enumerate(s.pairs):
            t1 = time.perf_counter()
            ref = driver.reference_fit(s, pair)
            sound = verdict(ctx, s, which, ref, s.first[which])
            t2 = time.perf_counter()
            # The control: the same fit over the cells the table holds on
            # the chip (placed long since: nothing is uploaded), in one pass.
            in_one_pass = driver._fit(s, pair, precision=one_pass)
            control = verdict(ctx, s, which, ref, in_one_pass)
            print(json.dumps({
                "seed": seed, "pair": pair, "reference_s": t2 - t1,
                "moved_by_the_fit": ref["moved"],
                **{f"sound_{k}": v for k, v in sound.items()},
                **{f"control_{k}": v for k, v in control.items()},
                "control_moved_parameters_by": float(max(
                    np.abs(a - b).max() for a, b in zip(in_one_pass, s.first[which])))}),
                flush=True)
        del s


if __name__ == "__main__":
    main()
