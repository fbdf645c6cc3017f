#!/usr/bin/env python3
"""The control of ``w2v-1bw.fit`` at the cell's own size, for a builder to
run ON THE CHIP (``python benchmark/tests/chip_controls_w2v.py --seeds
1``), beside ``chip_controls_als.py``. For each seed, from
``drivers/w2v.py``'s own set-up, replay, comparison and verdicts (the
lines that decide ``correct``):

- *sound*: the program as it is (every product of the scores and of the
  gradients float32-accurate): set-up's fit of each of the sweep's rates
  against the float64 replay of all its steps; ``correct`` has to come
  out true;
- *control*: the same corpus on the chip, the same start, the program's
  own steps with the operands of those products rounded to bfloat16
  (``score_dtype``: what ONE bfloat16 pass does to them, and what a
  program computing in the nearest precision below float32 would do; a
  v5e's compiler turns these skinny products into float32 multiplies and
  sums whatever ``precision`` they state, so the rounding is written
  out): the same gap, which has to come out well above its limit, and
  ``correct`` false.

A seed makes its own 3.5 GB table and places 5.2 GB of it, and the chip's
host hands freed pages back late (PR 32): run one process a seed
(``--seeds 1 --first-seed <n>``, in a loop).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=2_147_496_000)
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's rehearsal sizes (a CPU rehearsal of this script)")
    args = ap.parse_args()

    from benchmark import run
    from benchmark.drivers import program, w2v as driver
    import jax
    import jax.numpy as jnp

    program.enable_compile_cache()
    print(json.dumps({"devices": [str(d) for d in jax.devices()]}), flush=True)
    spec = run.load_spec(ROOT, "w2v-1bw.fit")

    def verdict(ctx, s, which, replayed, vectors, words):
        # The cell's own checks of one fit outside any window (so the
        # window's counters are given as what they have to be).
        s.timed = [(which, s.first[which], words)]
        cmp = driver.compare(s, replayed, vectors)
        checks = driver.verdicts(ctx, s, cmp, {
            "w2v.table_h2d_bytes": 0.0, "w2v.steps": float(s.steps),
            "w2v.fits": 1.0, "w2v.pairs": float(s.steps * s.batch),
            "table.token_rows_materialized": 0.0})
        ok = lambda c: c["value"] is not None and c["value"] <= c["limit"]
        return {"correct": all(ok(c) for c in checks),
                "failed_checks": [c["what"][:60] for c in checks if not ok(c)],
                "vector_gap": cmp["vector_gap"]}

    for seed in range(args.first_seed, args.first_seed + args.seeds):
        ctx = run.Context(spec, seed, 0.0, False, args.rehearse,
                          os.path.join(spec["home"], "out"))
        t0 = time.perf_counter()
        s = driver.setup(ctx)
        print(json.dumps({"seed": seed, "tokens": s.tokens,
                          "setup_s": time.perf_counter() - t0}), flush=True)
        for which, rate in enumerate(s.sweep):
            t1 = time.perf_counter()
            replayed = driver.replay(s, rate)
            t2 = time.perf_counter()
            words = replayed["words"]
            sound = verdict(ctx, s, which, replayed, s.first[which], words)
            # The control: the same fit over the corpus the table holds on
            # the chip (placed long since: nothing is uploaded), its
            # products' operands in bfloat16.
            rounded, _ = driver._fit(s, rate, score_dtype=jnp.bfloat16)
            control = verdict(ctx, s, which, replayed, rounded, words)
            print(json.dumps({
                "seed": seed, "rate": rate, "reference_s": t2 - t1,
                "reference_widest_movement": replayed["reference_widest_movement"],
                "loss_first": replayed["loss_first"], "loss_last": replayed["loss_last"],
                **{f"sound_{k}": v for k, v in sound.items()},
                **{f"control_{k}": v for k, v in control.items()}}), flush=True)
        del s


if __name__ == "__main__":
    main()
