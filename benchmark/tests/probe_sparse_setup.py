#!/usr/bin/env python3
"""What a Criteo-profile sparse fit cell would cost at the size the
memory floor asks for, timed once ON THE CHIP'S HOST (a builder's probe,
``python benchmark/tests/probe_sparse_setup.py --rows 13400000 --steps 160``;
no run of the benchmark calls it).

Per row count: seconds to draw the CSR arrays from the seed, to build the
one ``SparseVector`` per row that a ``Table`` needs (the program's only
public way in for sparse features), and for one whole
``LogisticRegression().fit(table)`` call per entry of ``--steps`` at dim
1,000,000, 39 non-zeros a row, batch 65,536; with the device's peak
memory after each.

Columns are drawn one from each of 39 equal strata of the dimension, so a
row's indices come sorted and distinct without a per-row ``unique``: the
cheapest honest construction, a lower bound on what set-up would pay.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

DIM, NNZ, BATCH, RATE = 1_000_000, 39, 65_536, 20.0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, nargs="+", default=[1_048_576])
    ap.add_argument("--steps", type=int, nargs="+", default=[1, 160])
    ap.add_argument("--seed", type=int, default=2_147_500_123)
    args = ap.parse_args()

    from benchmark import datagen
    from benchmark.drivers import program
    import jax
    from flinkml_tpu.linalg import SparseVector
    from flinkml_tpu.models import LogisticRegression
    from flinkml_tpu.table import Table

    program.enable_compile_cache()
    dev = jax.devices()[0]
    print(json.dumps({"devices": [str(d) for d in jax.devices()]}), flush=True)
    for n in args.rows:
        t0 = time.perf_counter()
        g = datagen.rng(args.seed, datagen.TAG_FEATURES, n)
        stratum = DIM // NNZ
        idx = (g.integers(0, stratum, size=(n, NNZ), dtype=np.int64)
               + np.arange(NNZ, dtype=np.int64) * stratum)
        val = g.standard_normal((n, NNZ))
        beta = np.zeros(DIM)
        active = g.choice(DIM, size=256, replace=False)
        beta[active] = g.standard_normal(256)
        y = ((val * beta[idx]).sum(axis=1) > 0).astype(np.float32)
        t1 = time.perf_counter()
        col = np.empty(n, object)
        for i in range(n):
            col[i] = SparseVector(DIM, idx[i], val[i])
        t2 = time.perf_counter()
        del idx, val            # the vectors hold their own sorted copies
        table = Table({"features": col, "label": y})
        line = {"rows": n, "csr_from_seed_s": t1 - t0, "sparse_vectors_s": t2 - t1}
        for steps in args.steps:
            est = (LogisticRegression().set_global_batch_size(BATCH).set_max_iter(steps)
                   .set_learning_rate(RATE).set_tol(0.0).set_seed(args.seed % (1 << 31)))
            t = time.perf_counter()
            coef = np.asarray(est.fit(table).coefficient)
            line[f"fit_{steps}_steps_s"] = time.perf_counter() - t
            line[f"finite_after_{steps}"] = bool(np.isfinite(coef).all())
            line[f"memory_peak_bytes_after_{steps}"] = int(
                (dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
        print(json.dumps(line), flush=True)
        del table, col


if __name__ == "__main__":
    main()
