#!/usr/bin/env python3
"""The controls of ``knn-mnist8m.transform`` at the cell's own size, for
a builder to run ON THE CHIP (``python benchmark/tests/chip_controls_knn.py
--seeds 3``), beside ``chip_controls.py`` and ``chip_controls_sparse.py``.
One process reads them all. For each seed, from ``drivers/knn.py``'s own
set-up, comparison and verdicts (the lines that decide ``correct``):

- *sound*: the program as it is (products at ``Precision.HIGHEST``):
  one call's predictions that differ from the float64 reference's vote
  on the queries it calls stable, the searched neighbour rows that are
  not the reference's (both limits are 0), the unstable and the
  undecided-order shares, the share of sampled queries whose
  neighbourhood is mixed; ``correct`` has to come out true;
- *the tolerance*: the widest gap between the squared distances the
  chip's search returns and the reference's, for the sampled queries'
  neighbours, beside the tolerance ``drivers/knn.tolerance`` allows;
- *control*: the same call and the same search with the products in ONE
  bfloat16 pass (the search's static ``precision`` argument at
  ``Precision.DEFAULT``, what a program computing in the nearest
  precision below float32 would do): the same counts, which have to come
  out well above 0, and ``correct`` false;
- *a uniform sample* (``--uniform``): the mixed share of 512 queries
  drawn without the generator's margin, which the cell's steered sample
  has to stand well above.
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
    ap.add_argument("--first-seed", type=int, default=2_147_495_000)
    ap.add_argument("--uniform", action="store_true",
                    help="also the mixed share of a uniform sample (one more "
                         "pass of the reference)")
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's rehearsal rows (a CPU rehearsal of this script)")
    args = ap.parse_args()

    from benchmark import datagen, run
    from benchmark.drivers import knn as driver, program
    from benchmark.reference import knn as reference
    import jax
    from flinkml_tpu.models import knn as program_knn

    program.enable_compile_cache()
    print(json.dumps({"devices": [str(d) for d in jax.devices()]}), flush=True)
    spec = run.load_spec(ROOT, "knn-mnist8m.transform")

    def verdict(ctx, s, pred, precision=None):
        # The cell's own checks of one call outside any window (so the
        # window's upload count is given as the 0 it has to be).
        cmp = driver.compare(ctx, s, 0, pred, precision)
        checks = driver.verdicts(ctx, s, cmp, pred, {"knn.model_h2d_bytes": 0.0})
        cmp["correct"] = all(c["value"] <= c["limit"] for c in checks)
        cmp["failed_checks"] = [c["what"][:60] for c in checks
                                if not c["value"] <= c["limit"]]
        return cmp

    for seed in range(args.first_seed, args.first_seed + args.seeds):
        ctx = run.Context(spec, seed, 0.0, False, args.rehearse,
                          os.path.join(spec["home"], "out"))
        t0 = time.perf_counter()
        s = driver.setup(ctx)
        t1 = time.perf_counter()
        sound_pred = driver._call(s, 0)
        t2 = time.perf_counter()
        sound = verdict(ctx, s, sound_pred)
        t3 = time.perf_counter()

        rows = driver.sample(ctx, s, 0)
        q = s.queries[rows]
        near, want_d2 = reference.k_nearest(q, s.x, s.k, shortlist=64)
        got_d2, got = driver.searched(s, q)
        same = got == near[:, :s.k]
        tol = driver.sample_tolerance(q, s.x[near])

        # The control: the model's own program with its products in one
        # bfloat16 pass, on the rows the model holds.
        one_pass = jax.lax.Precision.DEFAULT
        resident = s.model._on_device()
        ids = program_knn._knn_vote(
            jax.numpy.asarray(s.queries[:s.query_rows]), resident.features,
            resident.norms, resident.class_ids, k=s.k,
            num_classes=len(resident.classes),
            chunk=program_knn._chunk_rows(s.query_rows, s.model.CHUNK),
            tile=program_knn._tile_rows(s.train_rows, s.k), precision=one_pass)
        control_pred = resident.classes[np.asarray(ids)]
        control = verdict(ctx, s, control_pred, one_pass)
        line = {
            "seed": seed, "train_rows": s.train_rows, "setup_s": t1 - t0,
            "call_s": t2 - t1, "reference_s": t3 - t2,
            "sound_correct": sound["correct"],
            "sound_mismatch_stable": sound["mismatch_stable"],
            "sound_mismatch_all": sound["mismatch_all"],
            "sound_rows_mismatch": sound["rows_mismatch"],
            "sound_rows_mismatch_all": sound["rows_mismatch_all"],
            "control_correct": control["correct"],
            "control_failed_checks": control["failed_checks"],
            "control_mismatch_stable": control["mismatch_stable"],
            "control_mismatch_all": control["mismatch_all"],
            "control_rows_mismatch": control["rows_mismatch"],
            "control_predictions_changed": int(np.sum(control_pred != sound_pred)),
            "unstable_share": sound["unstable_share"],
            "unordered_share": sound["unordered_share"],
            "mixed_share": sound["mixed_share"],
            "gap_median": sound["gap_median"],
            "nearest_d2_median": sound["nearest_d2_median"],
            "neighbours_index_for_index": float(same.all(axis=1).mean()),
            "d2_gap_to_float64_max": float(np.max(np.abs(
                got_d2[same] - want_d2[:, :s.k][same]))),
            "d2_gap_over_tolerance_max": float(np.max(
                (np.abs(got_d2 - want_d2[:, :s.k]) / tol[:, None])[same])),
            "tolerance_median": float(np.median(tol))}
        if args.uniform:
            some = datagen.sample_rows(seed, s.query_rows, rows.size, 0)
            near_u, _ = reference.k_nearest(s.queries[some], s.x, s.k, shortlist=64)
            labels = s.y[near_u[:, :s.k]]
            line["uniform_mixed_share"] = float(
                (labels != labels[:, :1]).any(axis=1).mean())
        print(json.dumps(line), flush=True)
        # Two train sets do not fit the chip: drop this one's before the next.
        del s, resident, ids


if __name__ == "__main__":
    main()
