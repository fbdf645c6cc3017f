#!/usr/bin/env python3
"""The control of ``gbt-airline.fit`` at the cell's own size, for a builder
to run ON THE CHIP (``python benchmark/tests/chip_controls_gbt.py --seeds
1``), beside ``chip_controls_w2v.py``. For each seed, from
``drivers/gbt.py``'s own set-up, comparison and verdicts (the lines that
decide ``correct``):

- *sound*: the program as it is (``g`` and ``h`` in three bfloat16 parts
  against exact 0/1 operands: every product exact, the sums float32):
  set-up's fit of each of the sweep's rates against the float64 fit that
  follows its trees; ``correct`` has to come out true;
- *control*: the same table on the chip, the program's own fit with ``g``
  and ``h`` rounded to ONE bfloat16 part before a tree's histograms
  (``one_part``: what one bfloat16 pass does to a product of a gradient,
  and what a program computing in the nearest precision below float32
  would do): the same gaps, which have to come out well above their
  limits, and ``correct`` false;
- *planted*: the sound fit with every split of the last tree's LAST level
  (32 nodes at depth 6, where a node's gain is smallest beside the
  root's, and nothing follows that a wrong split would move) moved to a
  feature and one of its real edges drawn uniformly, seeded: a wrong
  choice of split, which ``split_regret`` alone guards (the reference
  follows whatever splits it is handed). Read: the verdict's
  ``split_regret`` (the widest over the nodes) and the quartiles of
  those 32 nodes' own regrets, what ONE such split reads.

A seed makes its own 6 GB table and the reference's children hold 5 GB
more, and the chip's host hands freed pages back late (PR 32): run one
process a seed (``--seeds 1 --first-seed <n>``, in a loop).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

GAPS = ("leaf_gap", "gain_gap", "split_regret")


def planted(s, fit: dict, edges, rng) -> dict:
    """``fit`` with its last tree's last level's splits drawn anew (the
    leaves and the gains are left as they were: only ``split_regret`` is
    read)."""
    import numpy as np

    first = (1 << (s.depth - 1)) - 1
    feats, thresholds = np.array(fit["feats"]), np.array(fit["thresholds"])
    real = np.isfinite(edges).sum(axis=1)
    for at in range(first, 2 * first + 1):
        f = int(rng.integers(s.features))
        feats[-1, at], thresholds[-1, at] = f, edges[f, rng.integers(real[f])]
    return {**fit, "feats": feats, "thresholds": thresholds}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=2_147_497_000)
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's rehearsal sizes (a CPU rehearsal of this script)")
    args = ap.parse_args()

    from benchmark import run
    from benchmark.drivers import gbt as driver, program
    from benchmark.reference import gbt as reference
    import jax
    import numpy as np

    program.enable_compile_cache()
    print(json.dumps({"devices": [str(d) for d in jax.devices()]}), flush=True)
    spec = run.load_spec(ROOT, "gbt-airline.fit")

    def verdict(ctx, s, which, fit):
        # The cell's own checks of one fit outside any window (so the
        # window's counters are given as what they have to be).
        s.timed = [(which, s.first[which])]
        t0 = time.perf_counter()
        cmp = driver.compare(s, fit)
        levels = float(s.trees * s.depth)
        checks = driver.verdicts(ctx, s, cmp, {
            "gbt.table_h2d_bytes": 0.0, "gbt.fits": 1.0, "gbt.trees": float(s.trees),
            "gbt.levels": levels, "gbt.product_levels": levels})
        ok = lambda c: c["value"] is not None and c["value"] <= c["limit"]
        return {"correct": all(ok(c) for c in checks),
                "failed_checks": [c["what"].split(": ")[-1][:12] for c in checks
                                  if not ok(c)],
                "reference_s": time.perf_counter() - t0,
                **{k: cmp.get(k) for k in GAPS + (
                    "loss_before", "loss_after", "regret_by_level",
                    "last_level_regrets", "edges_apart", "strangers", "base_gap")}}

    for seed in range(args.first_seed, args.first_seed + args.seeds):
        ctx = run.Context(spec, seed, 0.0, False, args.rehearse,
                          os.path.join(spec["home"], "out"))
        t0 = time.perf_counter()
        s = driver.setup(ctx)
        s.workers = 0 if s.rows < (1 << 22) else reference.WORKERS
        os.makedirs(driver.SCRATCH, exist_ok=True)
        print(json.dumps({"seed": seed, "rows": s.rows,
                          "setup_s": time.perf_counter() - t0}), flush=True)
        edges = reference.edges_of(s.x, s.bins, s.seed, s.sample_rows)
        for which, rate in enumerate(s.sweep):
            sound = verdict(ctx, s, which, s.first[which])
            # The control: the same fit over the table the chip holds
            # (placed long since: nothing is uploaded), one part of g and h.
            control = verdict(ctx, s, which, driver.one_part_fit(s, rate))
            wrong = verdict(ctx, s, which, planted(
                s, s.first[which], edges, np.random.default_rng(seed)))
            print(json.dumps({
                "seed": seed, "rate": rate,
                **{f"sound_{k}": v for k, v in sound.items()},
                **{f"control_{k}": v for k, v in control.items()},
                **{f"planted_{k}": wrong[k] for k in (
                    "correct", "failed_checks", "split_regret", "regret_by_level",
                    "last_level_regrets", "reference_s")}}), flush=True)
        del s


if __name__ == "__main__":
    main()
