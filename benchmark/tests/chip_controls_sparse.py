#!/usr/bin/env python3
"""The controls of ``lr-criteo.fit`` at the cell's own size, for a builder
to run ON THE CHIP (``python benchmark/tests/chip_controls_sparse.py
--seeds 3``), beside ``chip_controls.py`` (the dense cells'). One process
reads them all. For each seed, the numbers that decide ``correct``:

- *sound*: the widest coefficient gap of the cell's own fit (its rows,
  batch and steps, through ``LogisticRegression().fit`` of a ``CsrColumn``
  table, exactly ``drivers/fit_sparse.setup``) to float64 SGD replayed
  over the same row order (``reference/sparse_linear.py``);
- *control*: that replay at bfloat16 values, coefficient and multipliers
  with float32 sums between steps, against the float64 replay: what a
  program computing in the nearest precision below float32 would return;
- both also as the Euclidean norm of the difference, in case the widest
  gap (which a hot column's 45,000-term float32 sum decides) separates
  them less well;
- whether a second fit of the same call returns the same bits (the
  chip's scatter-add run to run).

``--vectors-rows N`` also fits the first N rows twice, as a ``CsrColumn``
and as an object column of ``SparseVector``s (the parent's only way in),
and says whether the coefficients are equal bit for bit.
"""

import argparse
import hashlib
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
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's rehearsal rows (a CPU rehearsal of this script)")
    ap.add_argument("--vectors-rows", type=int, default=0)
    args = ap.parse_args()

    from benchmark import run
    from benchmark.drivers import fit as dense, fit_sparse, program
    from benchmark.reference import sparse_linear as ref
    import jax

    program.enable_compile_cache()
    print(json.dumps({"devices": [str(d) for d in jax.devices()]}), flush=True)
    spec = run.load_spec(ROOT, "lr-criteo.fit")

    def sha(c):
        return hashlib.sha256(np.ascontiguousarray(c).tobytes()).hexdigest()[:16]

    for seed in range(args.first_seed, args.first_seed + args.seeds):
        ctx = run.Context(spec, seed, 0.0, False, args.rehearse,
                          os.path.join(spec["home"], "out"))
        t0 = time.perf_counter()
        s = fit_sparse.setup(ctx)
        t1 = time.perf_counter()
        again = dense._fit(ctx, s.table, s.batch, s.max_iter)
        t2 = time.perf_counter()
        i2, v2 = s.indices.reshape(s.rows, s.nnz), s.values.reshape(s.rows, s.nnz)
        order = ref.seeded_order(seed % (1 << 31), s.rows)
        rate = float(ctx.cell["learning_rate"])
        wide = ref.minibatch_sgd(i2, v2, s.dim, s.y, s.max_iter, rate, s.batch, order)
        t3 = time.perf_counter()
        low = ref.minibatch_sgd(i2, v2, s.dim, s.y, s.max_iter, rate, s.batch, order,
                                round_to=ref.to_bfloat16)
        got = s.coefs[0]
        print(json.dumps({
            "seed": seed, "rows": s.rows, "steps": s.max_iter,
            "setup_with_first_fit_s": t1 - t0, "second_fit_s": t2 - t1,
            "replay_s": t3 - t2, "second_fit_equal": bool(np.array_equal(got, again)),
            "largest_coef": float(np.max(np.abs(wide))),
            "sound_max": float(np.max(np.abs(got - wide))),
            "control_max": float(np.max(np.abs(low - wide))),
            "sound_l2": float(np.linalg.norm(got - wide)),
            "control_l2": float(np.linalg.norm(low - wide)),
            "coef_l2": float(np.linalg.norm(wide)),
            "positive_share": float(s.y.mean())}), flush=True)

        if args.vectors_rows and seed == args.first_seed:
            from flinkml_tpu.linalg import SparseVector
            from flinkml_tpu.table import Table

            n = min(args.vectors_rows, s.rows)
            part = s.table.slice(0, n)
            t0 = time.perf_counter()
            as_csr = dense._fit(ctx, part, s.batch, s.max_iter)
            t1 = time.perf_counter()
            vecs = np.empty(n, dtype=object)
            idx64, val64 = i2[:n].astype(np.int64), v2[:n].astype(np.float64)
            for r in range(n):
                vecs[r] = SparseVector._from_sorted(s.dim, idx64[r], val64[r])
            t2 = time.perf_counter()
            as_vec = dense._fit(ctx, Table({"features": vecs, "label": s.y[:n]}),
                                s.batch, s.max_iter)
            t3 = time.perf_counter()
            print(json.dumps({
                "vectors_rows": n, "csr_fit_s": t1 - t0, "build_vectors_s": t2 - t1,
                "vectors_fit_s": t3 - t2, "sha256_csr": sha(as_csr),
                "sha256_vectors": sha(as_vec),
                "bit_equal": bool(np.array_equal(as_csr, as_vec))}), flush=True)
        del s


if __name__ == "__main__":
    main()
