#!/usr/bin/env python3
"""The controls of ``kmeans-mnist8m.fit`` at the cell's own size, for a
builder to run ON THE CHIP (``python benchmark/tests/chip_controls_kmeans.py
--seeds 3``), beside ``chip_controls_knn.py``. One process reads them
all. For each seed, from ``drivers/kmeans.py``'s own set-up, comparison
and verdicts (the lines that decide ``correct``):

- *sound*: the program as it is (both products of a round at
  ``Precision.HIGHEST``): one fit's widest centroid gap to the float64
  reference after twenty rounds, and after ONE round of the trainer from
  the reference's centroids before its last; ``correct`` has to come
  out true;
- *control*: the same rows on the chip, the same start, the program's
  own whole-loop trainer with both products in ONE bfloat16 pass (its
  static ``precision`` argument at ``Precision.DEFAULT``: what a program
  computing in the nearest precision below float32 would do, and what
  the parent of PR 32 did): the same two gaps, which have to come out
  well above their limits, and ``correct`` false (the relative gap of
  the within-cluster sum of squares is printed beside them);
- ``--split``: the control once more with only the distances' product,
  and only the sums' product, in one pass: which of the two the
  comparison feels.

A seed makes its own 6.35 GB table, and the chip's host hands freed
pages back late: more than two seeds a process met its 40 GiB (PR 32).
Run one process a seed (``--seeds 1 --first-seed <n>``, in a loop).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_147_496_000)
    ap.add_argument("--split", action="store_true",
                    help="also one product at a time in one pass")
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's rehearsal rows (a CPU rehearsal of this script)")
    args = ap.parse_args()

    from benchmark import run
    from benchmark.drivers import kmeans as driver, program
    import jax
    from flinkml_tpu.models import kmeans as program_kmeans
    from flinkml_tpu.parallel import DeviceMesh

    program.enable_compile_cache()
    print(json.dumps({"devices": [str(d) for d in jax.devices()]}), flush=True)
    spec = run.load_spec(ROOT, "kmeans-mnist8m.fit")
    one_pass = jax.lax.Precision.DEFAULT

    def verdict(ctx, s, ref, centroids, precision=None):
        # The cell's own checks of one fit outside any window (so the
        # window's counters are given as what they have to be).
        s.timed = [(ref["seed"], s.first[ref["seed"]])]
        cmp = driver.compare(s, ref, centroids, precision)
        checks = driver.verdicts(ctx, s, cmp, {
            "kmeans.table_h2d_bytes": 0.0, "kmeans.rounds": float(s.max_iter),
            "kmeans.fits": 1.0})
        ok = lambda c: c["value"] is not None and c["value"] <= c["limit"]
        return {"correct": all(ok(c) for c in checks),
                "failed_checks": [c["what"][:50] for c in checks if not ok(c)],
                "gap": cmp["gap"], "round_gap": cmp["round_gap"],
                "cost_gap": cmp["cost_gap"],
                "moved_by_the_last_round": cmp["moved_by_the_last_round"],
                "close_rows_all_rounds": cmp["close_rows_all_rounds"],
                "close_rows_a_round_max": cmp["close_rows_a_round_max"]}

    for seed in range(args.first_seed, args.first_seed + args.seeds):
        ctx = run.Context(spec, seed, 0.0, False, args.rehearse,
                          os.path.join(spec["home"], "out"))
        t0 = time.perf_counter()
        s = driver.setup(ctx)
        t1 = time.perf_counter()
        fit_seed = s.seeds[0]
        ref = driver.reference_fit(s, fit_seed)
        sound = verdict(ctx, s, ref, s.first[fit_seed])
        t2 = time.perf_counter()

        # The control: the program's own trainer over the rows the table
        # holds on the chip (placed long since: nothing is uploaded).
        mesh = DeviceMesh()
        placed = program_kmeans._rows_on_mesh(s.table, "features", s.x, mesh)
        start = program_kmeans._start_centroids(s.x, s.k, fit_seed, s.init_mode)
        in_one_pass = program_kmeans._lloyd(placed, start, mesh, s.k, s.max_iter,
                                            precision=one_pass)
        control = verdict(ctx, s, ref, in_one_pass, one_pass)
        line = {"seed": seed, "rows": s.rows, "setup_s": t1 - t0,
                "reference_s": t2 - t1,
                **{f"sound_{k}": v for k, v in sound.items()},
                **{f"control_{k}": v for k, v in control.items()},
                "control_moved_centroids_by": float(np.abs(
                    in_one_pass - s.first[fit_seed]).max())}
        if args.split:
            for name, pair in (("distances", (one_pass, None)),
                               ("sums", (None, one_pass))):
                got = _split_fit(program_kmeans, placed, start, s, pair)
                v = verdict(ctx, s, ref, got)
                line.update({f"{name}_in_one_pass_gap": v["gap"],
                             f"{name}_in_one_pass_cost_gap": v["cost_gap"],
                             f"{name}_in_one_pass_correct": v["correct"]})
        print(json.dumps(line), flush=True)
        # Two tables do not fit the chip: drop this one's before the next.
        del s, placed


def _split_fit(program_kmeans, placed, start, s, precisions):
    """Twenty rounds with the distances' and the sums' product each at
    its own precision (None: the program's), on one device: the
    trainer's round written out, for the ``--split`` reading only."""
    import jax
    import jax.numpy as jnp

    from flinkml_tpu.ops import blas

    dist, sums = (p if p is not None else program_kmeans.PRODUCT_PRECISION
                  for p in precisions)

    def fit(rows, norms, mask, c0):
        # The table is an ARGUMENT: closed over, it would be lowered as a
        # 6.35 GB constant of the program.
        def body(_, c):
            d2 = blas.squared_distances(rows, c, precision=dist, xs_sq=norms)
            onehot = jax.nn.one_hot(jnp.argmin(d2, -1), s.k, dtype=c.dtype) \
                * mask[:, None]
            return program_kmeans._moved(
                jnp.matmul(onehot.T, rows, precision=sums), onehot.sum(0), c)

        return jax.lax.fori_loop(0, s.max_iter, body, c0)

    return np.asarray(jax.jit(fit)(*placed, jnp.asarray(start)))


if __name__ == "__main__":
    main()
