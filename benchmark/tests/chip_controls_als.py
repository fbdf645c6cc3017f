#!/usr/bin/env python3
"""The control of ``als-yahoomusic.fit`` at the cell's own size, for a
builder to run ON THE CHIP (``python benchmark/tests/chip_controls_als.py
--seeds 1``), beside ``chip_controls_fm.py``. For each seed, from
``drivers/als.py``'s own set-up, comparison and verdicts (the lines that
decide ``correct``):

- *sound*: the program as it is (the Gram and right-hand-side products at
  ``Precision.HIGHEST``): set-up's fit of the sweep's last value against
  the float64 solves of the sampled users and items; ``correct`` has to
  come out true;
- *control*: the same ratings on the chip, the same start, the program's
  own half-steps with those products in ONE bfloat16 pass (their static
  ``precision`` at ``Precision.DEFAULT``: what a program computing in the
  nearest precision below float32 would do): the same gaps, which have to
  come out well above their limit, and ``correct`` false.

A seed makes its own 3 GB table and places 5 GB of it, and the chip's
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
    from benchmark.drivers import als as driver, program
    import jax

    program.enable_compile_cache()
    print(json.dumps({"devices": [str(d) for d in jax.devices()]}), flush=True)
    spec = run.load_spec(ROOT, "als-yahoomusic.fit")
    one_pass = jax.lax.Precision.DEFAULT

    def verdict(ctx, s, which, fit):
        # The cell's own checks of one fit outside any window (so the
        # window's counters are given as what they have to be).
        s.timed = [(which, s.first[which])]
        cmp = driver.compare(s, s.sweep[which], fit)
        checks = driver.verdicts(ctx, s, cmp, {
            "als.table_h2d_bytes": 0.0, "als.half_steps": 2.0 * s.max_iter,
            "als.fits": 1.0})
        ok = lambda c: c["value"] is not None and c["value"] <= c["limit"]
        return {"correct": all(ok(c) for c in checks),
                "failed_checks": [c["what"][:60] for c in checks if not ok(c)],
                "user_gap": cmp["user_gap"], "item_gap": cmp["item_gap"],
                "train_rmse_after": cmp.get("train_rmse_after")}

    for seed in range(args.first_seed, args.first_seed + args.seeds):
        ctx = run.Context(spec, seed, 0.0, False, args.rehearse,
                          os.path.join(spec["home"], "out"))
        t0 = time.perf_counter()
        s = driver.setup(ctx)
        print(json.dumps({"seed": seed, "ratings": s.ratings,
                          "setup_s": time.perf_counter() - t0}), flush=True)
        which = len(s.sweep) - 1
        t1 = time.perf_counter()
        sound = verdict(ctx, s, which, s.first[which])
        t2 = time.perf_counter()
        # The control: the same fit over the orders the table holds on the
        # chip (placed long since: nothing is uploaded), in one pass.
        in_one_pass = driver._fit(s, s.sweep[which], precision=one_pass)
        control = verdict(ctx, s, which, in_one_pass)
        print(json.dumps({
            "seed": seed, "reg": s.sweep[which], "reference_s": t2 - t1,
            **{f"sound_{k}": v for k, v in sound.items()},
            **{f"control_{k}": v for k, v in control.items()}}), flush=True)
        del s


if __name__ == "__main__":
    main()
