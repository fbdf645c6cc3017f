#!/usr/bin/env python3
"""The controls of ``mlp-mnist8m.fit`` at the cell's own size, for a
builder to run ON THE CHIP (``python benchmark/tests/chip_controls_mlp.py
--seeds 1``), beside ``chip_controls_gbt.py``. For each seed, from
``drivers/mlp.py``'s own set-up, comparison and verdicts (the lines that
decide ``correct``), at each of the sweep's rates, the table on the chip
all along (placed once: nothing is uploaded again):

- *sound*: the program as the configuration states it (``mixed``: bfloat16
  operands, float32 sums): set-up's fit; every check has to pass;
- *float32*: the program with no policy (float32 operands, ``HIGHEST``
  products): the better side, which has to pass every check;
- *four_bits*: the control one precision lower, every product's operands
  cut to FOUR bits of significand (float8-e4m3's for bfloat16's eight):
  has to fail, and fails ``start_loss_gap``, ``grad_gap`` and
  ``param_change_gap``;
- *half_window*: the second half of every window's rows left out of the
  loop's step (the function outside the loop reads all of them, so only
  what the timed fit returned can show it): has to fail
  ``param_change_gap`` at the sweep's lowest rate, the one ``check``
  follows;
- *frozen_leaf*: one array (``b_3``) never updated: has to fail
  ``param_change_gap``, which reads 1;
- *other_start*: the fit from the next seed's start: has to fail
  ``first_loss_gap``.

The last four are put into the program from the benchmark's side
(``drivers.mlp.planted``). ``--dump`` writes every variant's curve and its
gaps a leaf under ``chiprun_out/mlp_controls/``. A seed makes its own 6 GB
table: run one process a seed where the host is short (``--seeds 1
--first-seed <n>``, in a loop).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

NAMES = ("start_gap", "first_loss_gap", "loss_curve_gap",
         "param_change_gap", "trained", "start_loss_gap", "grad_gap", "refit_gap")
#: ``(the fault planted, precision=)`` a variant.
VARIANTS = {"sound": (None, "the configuration's"), "float32": (None, None),
            "four_bits": ("four_bits", "mixed"),
            "half_window": ("half_window", "the configuration's"),
            "frozen_leaf": ("frozen_leaf", "the configuration's"),
            "other_start": ("other_start", "the configuration's")}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=2_147_497_000)
    ap.add_argument("--dump", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's rehearsal sizes (a CPU rehearsal of this script)")
    args = ap.parse_args()

    from benchmark import run
    from benchmark.drivers import mlp as driver, program
    import jax
    import numpy as np

    program.enable_compile_cache()
    print(json.dumps({"devices": [str(d) for d in jax.devices()]}), flush=True)
    spec = run.load_spec(ROOT, "mlp-mnist8m.fit")

    def verdict(ctx, s, at, which, cmp):
        # The cell's own checks of one fit outside any window (so the
        # window's fits and counters are given as what they have to be),
        # each held to its limit as ``run.py`` holds it.
        s.timed = [(which, s.first[which])]
        checks = driver.verdicts(ctx, s, at, cmp, {
            "mlp.table_h2d_bytes": 0.0, "mlp.fits": 1.0,
            "mlp.steps": float(s.steps), "mlp.policy_steps": float(s.steps)})
        ok = lambda c: c["value"] is not None and c["value"] <= c["limit"]
        return [next((k for k in NAMES if k in c["what"]), c["what"][:24])
                for c in checks if not ok(c)]

    for seed in range(args.first_seed, args.first_seed + args.seeds):
        ctx = run.Context(spec, seed, 0.0, False, args.rehearse,
                          os.path.join(spec["home"], "out"))
        t0 = time.perf_counter()
        s = driver.setup(ctx)
        at = driver.start_of(s)
        print(json.dumps({"seed": seed, "rows": s.rows,
                          "setup_s": time.perf_counter() - t0,
                          "start_off_rule": at["start_off_rule"],
                          "start_gap": at["start_gap"]}), flush=True)
        lines = [{"seed": seed, "rate": rate} for rate in s.sweep]
        dump = {"seed": seed, "sweep": s.sweep, "reference": {}, "variants": {}}
        sound_window = None
        for name, (fault, precision) in VARIANTS.items():
            with driver.planted(fault):
                if fault in (None, "four_bits"):
                    window = driver.window_gaps(s, at, precision)
                    sound_window = window if name == "sound" else sound_window
                else:   # the function outside the loop is the sound one's
                    window = sound_window or driver.window_gaps(s, at, precision)
                for which, rate in enumerate(s.sweep):
                    t0 = time.perf_counter()
                    fit = (s.first[which] if name == "sound"
                           else driver.public_fit(s, rate, precision))
                    fit_s = time.perf_counter() - t0
                    cmp = {**window, **driver.fit_gaps(s, at, fit)}
                    lines[which].update(
                        {f"{name}_{k}": cmp[k] for k in NAMES + (
                            "mean_loss_gap", "mean_curve_gap", "widest_loss_gap",
                            "last_loss",
                            "param_change_gap_by_leaf", "grad_gap_by_layer")
                         if k in cmp})
                    lines[which].update({f"{name}_failed": verdict(ctx, s, at, which, cmp),
                                         f"{name}_fit_s": fit_s})
                    dump["variants"][f"{name}@{rate}"] = {
                        "losses": np.asarray(fit["losses"]).tolist(),
                        "moved_by_leaf": [float(np.linalg.norm(
                            np.asarray(p, np.float64) - a))
                            for p, a in zip(fit["params"], at["start"])], **cmp}
        for rate, (_, curve) in s.reference_fits.items():
            dump["reference"][str(rate)] = curve.tolist()
        for line in lines:
            print(json.dumps(line), flush=True)
        if args.dump:
            out = os.path.join(ROOT, "chiprun_out", "mlp_controls")
            os.makedirs(out, exist_ok=True)
            with open(os.path.join(out, f"{seed}.json"), "w") as f:
                json.dump(dump, f)
        del s


if __name__ == "__main__":
    main()
