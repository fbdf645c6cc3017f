#!/usr/bin/env python3
"""The controls at the cells' own sizes, for a builder to run ON THE CHIP
(``python benchmark/tests/chip_controls.py --seeds 12``): for each seed,
the number that decides ``correct`` as the sound program gives it and as
the control gives it, so a limit can be set between the largest of the
first and the smallest of the second. One process reads them all. The
same comparisons at a test's size are ``test_control.py``.

- chain-a9a: ``rawPrediction``'s widest gap to the float64 chain over
  the cell's sample of one table of the cell's rows; control: the
  program's own ``precision_scope("mixed_inference")`` (bfloat16).
- lr-a9a: the widest coefficient gap of the cell's own fit (its rows,
  batch and steps, through ``LogisticRegression().fit``) to float64 SGD
  replayed over the same row order; control: that replay at bfloat16
  features, coefficient and multipliers with float32 accumulation (the
  program's own ``precision`` policy is momentum SGD, another update).
  ``--which`` picks one configuration; ``--control-seeds`` runs the
  control on the first few seeds only.
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
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_147_490_000)
    ap.add_argument("--rows-divisor", type=int, default=1,
                    help="shrink rows (a CPU rehearsal of this script)")
    ap.add_argument("--which", choices=("chain", "lr", "both"), default="both")
    ap.add_argument("--control-seeds", type=int, default=None)
    args = ap.parse_args()

    from benchmark import datagen
    from benchmark.drivers import chain_model, program
    from benchmark.reference import chain as chain_ref
    from benchmark.reference import linear as linear_ref
    import jax
    from flinkml_tpu import pipeline_fusion
    from flinkml_tpu.models import LogisticRegression
    from flinkml_tpu.table import Table

    program.enable_compile_cache()
    print(json.dumps({"devices": [str(d) for d in jax.devices()]}), flush=True)

    def cell(name):
        with open(os.path.join(ROOT, "benchmark", "workloads", f"{name}.json")) as f:
            return json.load(f)

    tr, ft = cell("chain-a9a.transform"), cell("lr-a9a.fit")
    with open(os.path.join(ROOT, "benchmark", "configs", "lr-a9a.json")) as f:
        lr_cfg = json.load(f)
    rows = tr["rows"] // args.rows_divisor
    lr_rows = lr_cfg["rows"] // args.rows_divisor
    batch = lr_cfg["global_batch_size"] // args.rows_divisor
    steps, rate = ft["max_iter"], ft["learning_rate"]
    n_control = args.seeds if args.control_seeds is None else args.control_seeds
    sound, control = {"chain": [], "lr": []}, {"chain": [], "lr": []}
    for i in range(args.seeds):
        seed = args.first_seed + i
        line = {"seed": seed}
        if args.which in ("chain", "both"):
            md = datagen.chain_model_data(seed, 123)
            model = chain_model.build(md)
            x = datagen.normal_matrix(seed, datagen.TAG_FEATURES, rows, 123)
            idx = datagen.sample_rows(seed, rows, tr["sample_rows"], 0)

            def gap():
                (out,) = model.transform(Table({"features": x}))
                c = chain_ref.compare(md, x[idx], np.asarray(out.column("prediction"))[idx],
                                      np.asarray(out.column("rawPrediction"))[idx])
                return c["raw_max_abs_err"], c["pred_mismatch_away"]

            s = gap()
            sound["chain"].append(s[0])
            line["chain_sound"] = s
            if i < n_control:
                with pipeline_fusion.precision_scope("mixed_inference"):
                    c = gap()
                control["chain"].append(c[0])
                line["chain_control"] = c
            del x, model
        if args.which in ("lr", "both"):
            x = datagen.normal_matrix(seed, datagen.TAG_FEATURES, lr_rows, 123)
            y = datagen.planted_labels(seed, x)
            est = (LogisticRegression().set_global_batch_size(batch).set_max_iter(steps)
                   .set_learning_rate(rate).set_tol(0.0).set_seed(seed % (1 << 31)))
            t0 = time.perf_counter()
            got = np.asarray(est.fit(Table({"features": x, "label": y})).coefficient,
                             np.float64)
            t1 = time.perf_counter()
            order = linear_ref.seeded_order(seed % (1 << 31), lr_rows)
            want = linear_ref.minibatch_sgd(x, y, steps, rate, batch, order)
            t2 = time.perf_counter()
            sound["lr"].append(float(np.max(np.abs(got - want))))
            line.update(lr_sound=sound["lr"][-1], lr_fit_s=t1 - t0, lr_reference_s=t2 - t1,
                        lr_largest_coef=float(np.max(np.abs(want))))
            if i < n_control:
                low = linear_ref.minibatch_sgd(x, y, steps, rate, batch, order,
                                               round_to=linear_ref.to_bfloat16)
                control["lr"].append(float(np.max(np.abs(low - want))))
                line["lr_control"] = control["lr"][-1]
            del x, y
        print(json.dumps(line), flush=True)
    for k in ("chain", "lr"):
        if sound[k] and control[k]:
            print(json.dumps({"number": k, "seeds": len(sound[k]),
                              "control_seeds": len(control[k]),
                              "sound_largest": max(sound[k]),
                              "control_smallest": min(control[k]),
                              "ratio": min(control[k]) / max(sound[k])}), flush=True)


if __name__ == "__main__":
    main()
