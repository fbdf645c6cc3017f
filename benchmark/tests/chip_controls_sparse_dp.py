#!/usr/bin/env python3
"""The control of ``lr-criteo-dp4.fit``'s one limit at the cell's own
size, for a builder to run on a host with 20 GB to spare (``python
benchmark/tests/chip_controls_sparse_dp.py --seeds 2``): it is NumPy
alone and needs no chip, only the machine's memory (45.8 M rows are 14.3
GB of cells). The *sound* side of the comparison is the cell's own run,
whose ``check`` line prints the fit's gap to the float64 replay. Here,
for each seed:

- *control*: the SHARDED replay (``reference/sparse_linear_dp.py``) at
  bfloat16 values, coefficient and multipliers with float32 sums between
  steps, against the float64 replay of the same order: what a program
  computing in the nearest precision below float32 would return;
- *one worker's order*: the float64 replay of the ONE-worker order over
  the same rows against the four workers': what a fit that sharded by
  another rule would return.

Both have to fail ``limits.coef_gap`` by a wide margin.
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
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=2_147_495_500)
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's rehearsal rows, batch and steps")
    ap.add_argument("--threads", type=int, default=16)
    args = ap.parse_args()

    from benchmark import datagen_criteo
    from benchmark.reference import sparse_linear_dp as ref

    def read(*parts):
        with open(os.path.join(ROOT, "benchmark", *parts)) as f:
            return json.load(f)

    cell = read("workloads", "lr-criteo-dp4.fit.json")
    config = read("configs", "lr-criteo-dp4.json")
    size = {**config, **cell, **(cell["rehearse"] if args.rehearse else {})}
    rows, dim, nnz = int(size["rows"]), int(size["dim"]), int(size["nnz"])
    batch, steps = int(size["global_batch_size"]), int(size["max_iter"])
    workers, rate = int(config["workers"]), float(cell["learning_rate"])
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        t0 = time.perf_counter()
        _, indices, values, y = datagen_criteo.criteo_rows(
            seed, rows, dim, config["field_cardinalities"],
            int(config["field_stratum"]))
        i2, v2 = indices.reshape(rows, nnz), values.reshape(rows, nnz)
        order = ref.seeded_order(seed % (1 << 31), rows)
        t1 = time.perf_counter()
        wide = ref.minibatch_sgd(i2, v2, dim, y, steps, rate, batch, order, workers,
                                 threads=args.threads)
        t2 = time.perf_counter()
        low = ref.minibatch_sgd(i2, v2, dim, y, steps, rate, batch, order, workers,
                                round_to=ref.to_bfloat16, threads=args.threads)
        alone = ref.minibatch_sgd(i2, v2, dim, y, steps, rate, batch, order, 1,
                                  threads=args.threads)
        print(json.dumps({
            "seed": seed, "rows": rows, "steps": steps, "workers": workers,
            "data_s": t1 - t0, "replay_s": t2 - t1,
            "limit": cell["limits"]["coef_gap"],
            "largest_coef": float(np.max(np.abs(wide))),
            "columns_touched": int(np.count_nonzero(wide)),
            "control_max": float(np.max(np.abs(low - wide))),
            "control_l2": float(np.linalg.norm(low - wide)),
            "one_worker_order_max": float(np.max(np.abs(alone - wide))),
            "coef_l2": float(np.linalg.norm(wide))}), flush=True)
        del indices, values, i2, v2, y, order


if __name__ == "__main__":
    main()
