"""``driver: mlp`` — whole ``MLPClassifier(precision, layers, batch,
steps).fit(Table)`` calls, back to back, on ONE host ``Table`` of
``train_rows x 784`` float32 images and their class ids
(``datagen_mnist``: the profile of ``kmeans-mnist8m`` and ``knn-mnist8m``,
synthesised) whose rows set-up's first fit placed on the chip: the
learning rate of a classifier swept over an archive of digits that stays
there. The cell's ``sweep`` lists the rates, taken in turn from the one
the seed names; the twelve parameter arrays and the loss of every step
are read back every fit. A closed loop: a new fit starts while the window
is open and the one in flight always finishes.

Set-up builds the estimator FIRST (a program whose ``MLPClassifier``
refuses ``precision`` stops there, a ``ValueError`` before any table is
made: the parent of PR 52), then makes the table and fits each rate once:
the first fit places the rows in the seeded order and warms the one
program (the rate is an operand of it), and the window may upload nothing
of the table again. The configuration's file gives ``layers``,
``global_batch_size``, ``max_iter``, ``precision`` and ``train_rows``; the
cell's file ``sweep`` and ``limits``.

``correct`` is decided after the window. ``reference/mlp.py`` (``jax.numpy``
float32 at ``highest``; **it runs on the chip**, beside the resident table:
a fit of this size is out of a host's reach inside a run) is handed the
host's table and takes the seeded order and draws the start itself
(``reference.start``: the estimator takes no start from its caller, so the
program's own, ``_mlp_table.start_params``, is held to the reference's
draw, ``start_gap``, and the timed fit's first loss to the reference's
there, ``first_loss_gap``). Each number beside its limit (the cell's
``limits_from``). From what the timed program ``mlp_fit`` itself returned,
the LAST timed fit at the lowest rate the window ran, against the
reference run at that rate from its own start over the same windows (at
the sweep's higher rate Adam's first step, 1e-3 on every weight, throws
the loss from 2.4 to 3.0-5.1, and over the steps that follow two sound
runs drift as far apart as a planted fault moves one: PERF.md section 2
has the readings; those fits run the same program, the rate an operand,
and are held to set-up's bit for bit):

- ``first_loss_gap``: ``|loss(0) - loss_ref(0)|``, the timed curve's first
  step: the start and the first window of the timed program;
- ``loss_curve_gap``: the MEDIAN over its steps of ``|loss(t) -
  loss_ref(t)|``: that the fit follows the reference's descent, and no
  more (Adam's first steps move every weight by the rate in the direction
  of a gradient's sign, bfloat16's rounding turns signs, and two sound
  runs part by 3e-5 to 2.4e-3 here, as far as half a window left out
  moves one; the mean and the widest gap are a few steps' spikes:
  printed, not judged);
- ``param_change_gap``: ``|p - p_ref|_F / |p_ref - p_start|_F`` an array of
  the model, the largest over the six ``W_l`` and the hidden layers' five
  ``b_l``: every leaf moved as the reference moved it, the number that
  tells a fault of the loop's step from the sound side (the output
  layer's bias, ten numbers that the reference itself moves by 3e-4 each
  under a gradient that hovers about zero, reads 0.01 to 0.64 on the
  sound side: printed, not judged);
- ``trained``: its last loss over its first;
- ``refit_gap``: timed fits (of every rate) that differ in any bit (a
  parameter, a step's loss) from set-up's fit of their rate.

From one window outside the loop (the step's own function,
``_mlp_table.loss_and_gradients`` under ``_mlp_table.product_of`` of the
configuration's policy, jitted here over step 0's rows at the start):

- ``start_loss_gap``: the root mean square over the 16,384 rows of a
  row's loss less the reference's: the forward pass's precision;
- ``grad_gap``: ``|g - g_ref|_F / |g_ref|_F`` a layer (weights and biases
  together) against the reference's written-out backward pass, the
  largest of them: the backward pass's precision.

Exactly: the table's upload counter unmoved; steps, fits and steps under
a policy as counted.

``flops_bytes_mlp.step`` is the count under ``mlp_step_mfu``.
:func:`planted` runs the program with a fault put into it from this side
(``FAULTS``: every product's operands cut to four bits of significand, a
precision below the configuration's; half of every window's rows left
out of the step; one array never updated; another seed's start), for
``tests/chip_controls_mlp.py`` (a builder on the chip) and the tests;
nothing of the program knows of it. Rehearse the cell on a CPU (40,000
rows; every width kept; two minutes)::

    JAX_PLATFORMS=cpu python benchmark/run.py --workload mlp-mnist8m.fit \
        --seed 2147493105 --seconds 1 --trace 1 --rehearse
"""

from __future__ import annotations

import contextlib
import json
import time
import types

import numpy as np

from benchmark import datagen_mnist
from benchmark.reference import mlp as reference
from flinkml_tpu.table import Table

FEATURES, LABEL = "features", "label"
#: ``precision=`` of :func:`estimator`: the policy the configuration's file
#: names (None is a policy too: none).
CONFIGURED = "the configuration's"
#: The array :data:`FAULTS`' ``frozen_leaf`` never updates: ``b_3``.
FROZEN = 5


def four_bits(a):
    """``a`` rounded to four bits of significand (float8-e4m3's, at
    bfloat16's exponent range) and handed on as bfloat16: what a program
    computing one precision below bfloat16's eight would hand a product."""
    import jax
    import jax.numpy as jnp

    drop = 24 - 4
    bits = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
    bits = (bits + jnp.uint32(1 << (drop - 1))) & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(bits, jnp.float32).astype(jnp.bfloat16)


def _four_bit_products(product_of):
    def cut(policy):
        dot = product_of(policy)
        return lambda a, b, contract=(1, 0): dot(four_bits(a), four_bits(b), contract)

    return cut


def _half_windows(windows):
    def half(x, y, w, t, local_bs):
        import jax.numpy as jnp

        xb, yb, wb = windows(x, y, w, t, local_bs)
        return xb, yb, jnp.where(jnp.arange(local_bs) < local_bs // 2, wb, 0.0)

    return half


def _frozen_leaf(adam_update):
    def update(params, m, v, grads, step, lr):
        moved, m, v = adam_update(params, m, v, grads, step, lr)
        return moved[:FROZEN] + (params[FROZEN],) + moved[FROZEN + 1:], m, v

    return update


def _other_start(start_params):
    return lambda layers, seed, mesh: start_params(layers, seed + 1, mesh)


#: What :func:`planted` puts in the program's place, by the name of
#: ``models/_mlp_table``'s function it wraps: a precision below the
#: configuration's (the control), two faults of the loop's step that no
#: number taken outside the loop can see, and a fit from another seed's start.
FAULTS = {"four_bits": {"product_of": _four_bit_products},
          "half_window": {"_windows": _half_windows},
          "frozen_leaf": {"adam_update": _frozen_leaf},
          "other_start": {"start_params": _other_start}}


@contextlib.contextmanager
def planted(fault):
    """The program with ``FAULTS[fault]`` in it (None: as it is) while the
    block runs: functions of ``models/_mlp_table`` wrapped from here, the
    trainers it built before dropped on the way in and on the way out."""
    from flinkml_tpu.models import _mlp_table

    kept = {name: getattr(_mlp_table, name) for name in FAULTS.get(fault, {})}
    _mlp_table._trainer.cache_clear()
    for name, wrap in FAULTS.get(fault, {}).items():
        setattr(_mlp_table, name, wrap(kept[name]))
    try:
        yield
    finally:
        for name, was in kept.items():
            setattr(_mlp_table, name, was)
        _mlp_table._trainer.cache_clear()


def estimator(s, rate: float, precision=CONFIGURED):
    from flinkml_tpu.models import MLPClassifier

    policy = s.precision if precision == CONFIGURED else precision
    return (MLPClassifier(precision=policy).set_features_col(FEATURES)
            .set_label_col(LABEL).set_layers(s.layers)
            .set_global_batch_size(s.batch).set_max_iter(s.steps).set_tol(0.0)
            .set_learning_rate(float(rate)).set_seed(s.seed))


def public_fit(s, rate: float, precision=CONFIGURED) -> dict:
    """One unit through ``Estimator.fit``: the model's arrays."""
    model = estimator(s, rate, precision).fit(s.table)
    return {"rate": rate, "params": model._weights, "losses": model.loss_history}


def setup(ctx):
    s = types.SimpleNamespace()
    s.layers = [int(d) for d in ctx.config["layers"]]
    s.batch, s.steps = int(ctx.size("global_batch_size")), int(ctx.size("max_iter"))
    s.precision = ctx.config["precision"]
    s.rows = int(ctx.size("train_rows"))
    s.seed = ctx.seed % (1 << 31)
    s.sweep = [float(v) for v in ctx.cell["sweep"]]
    estimator(s, s.sweep[0])  # see the docstring: before any table
    t0 = time.perf_counter()
    s.x, labels, _ = datagen_mnist.images(ctx.seed, datagen_mnist.TAG_TRAIN, s.rows)
    s.y = labels.astype(np.int32)
    s.table = Table({FEATURES: s.x, LABEL: s.y})
    print(json.dumps({"phase": "data", "seconds": time.perf_counter() - t0}),
          flush=True)
    # Each rate's fit once: the first places the table and warms the one
    # program (the window's zero-compile count checks that it did), and
    # each is what every timed fit of its rate has to equal.
    s.first = []
    for rate in s.sweep:
        t0 = time.perf_counter()
        s.first.append(public_fit(s, rate))
        print(json.dumps({"phase": "warm-fit", "rate": rate,
                          "seconds": time.perf_counter() - t0,
                          "first_loss": float(s.first[-1]["losses"][0]),
                          "last_loss": float(s.first[-1]["losses"][-1])}), flush=True)
    return s


def window(ctx, s):
    walls, s.timed = [], []
    t_open = time.perf_counter()
    while True:
        which = (s.seed + len(walls)) % len(s.sweep)
        t0 = time.perf_counter()
        with ctx.unit("fit", fits=1, steps=s.steps, samples=s.steps * s.batch):
            s.timed.append((which, public_fit(s, s.sweep[which])))
        now = time.perf_counter()
        walls.append(now - t0)
        if now - t_open >= ctx.seconds:
            break
    return {"work": len(walls) * s.steps * s.batch, "wall_s": now - t_open,
            "attempted": len(walls), "failed": 0, "unit_walls_s": walls}


def start_of(s) -> dict:
    """The reference's start and its loss and gradient of step 0's window
    there; how far the start is from the rule the configuration states
    (He-scaled normal weights, zero biases); how far the program's start
    is from it."""
    from flinkml_tpu.models import _mlp_table
    from flinkml_tpu.parallel import DeviceMesh

    start = reference.start(s.layers, s.seed)
    program = _mlp_table.start_params(s.layers, s.seed, DeviceMesh())
    order = reference.seeded_order(s.seed, s.rows)
    rows = reference.step_rows(order, s.batch, 0)
    loss, grads = reference.loss_and_gradients(start, s.x[rows], s.y[rows])
    by_row = np.asarray(reference.row_losses(start, s.x[rows], s.y[rows]))
    scale = max(abs(float(w.std()) / np.sqrt(2.0 / w.shape[0]) - 1.0)
                for w in start[0::2])
    return {"start": start, "order": order, "rows": rows, "loss": float(loss),
            "grads": tuple(np.asarray(g) for g in grads), "row_losses": by_row,
            "start_off_rule": max(scale, max(float(np.abs(b).max())
                                             for b in start[1::2])),
            "start_gap": max(reference.relative_gaps(program, start))}


def layer_gaps(got, want) -> list:
    """``|g - g_ref|_F / |g_ref|_F`` a layer, weights and biases together."""
    pairs = lambda g: [np.concatenate([np.ravel(g[i]), np.ravel(g[i + 1])])
                       for i in range(0, len(g), 2)]
    return reference.relative_gaps(pairs(got), pairs(want))


def window_gaps(s, at: dict, precision=CONFIGURED) -> dict:
    """The step's own loss a row and gradient of step 0's window at the
    start (``_mlp_table.loss_and_gradients`` under ``_mlp_table.
    product_of`` of ``precision``, the configuration's where not given:
    the functions ``mlp_fit``'s step calls, jitted here) against the
    reference's from ``at`` (:func:`start_of`)."""
    import jax
    import jax.numpy as jnp
    from flinkml_tpu.models import _mlp_table

    dot = _mlp_table.product_of(estimator(s, s.sweep[0], precision).precision)

    def program(params, xb, yb):
        losses, sums = _mlp_table.loss_and_gradients(
            params, xb, yb, jnp.ones(xb.shape[0], jnp.float32), True, dot)
        return losses, tuple(g / xb.shape[0] for g in sums)

    by_row, grads = jax.device_get(jax.jit(program)(
        at["start"], s.x[at["rows"]], s.y[at["rows"]]))
    by_layer = layer_gaps(grads, at["grads"])
    return {"loss_ref": at["loss"],
            "mean_loss_gap": abs(float(np.mean(by_row)) - at["loss"]),
            "start_loss_gap": float(np.sqrt(np.mean(np.square(
                by_row - at["row_losses"])))),
            "grad_gap": max(by_layer), "grad_gap_by_layer": by_layer}


def reference_fit(s, at: dict, rate: float):
    """``(params, losses)`` of the reference at ``rate`` from its own start
    over the table's windows: computed once a set-up and kept in ``s``."""
    kept = s.__dict__.setdefault("reference_fits", {})
    if rate not in kept:
        t0 = time.perf_counter()
        kept[rate] = reference.fit(s.x, s.y, at["order"], at["start"], rate,
                                   s.steps, s.batch)
        print(json.dumps({"phase": "reference", "rate": rate,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return kept[rate]


def fit_gaps(s, at: dict, fit: dict) -> dict:
    """What one fit of the program (``rate``, ``params``, ``losses``: what
    ``mlp_fit`` returned) reads against :func:`reference_fit` at its
    rate."""
    want_params, want = reference_fit(s, at, fit["rate"])
    losses = np.asarray(fit["losses"])
    whole = losses.shape == want.shape and bool(np.isfinite(losses).all())
    gaps = np.abs(losses - want) if whole else None
    moved = [float(np.linalg.norm(np.asarray(w, np.float64) - a))
             for w, a in zip(want_params, at["start"])]
    by_leaf = [float(np.linalg.norm(np.asarray(p, np.float64) - w) / max(m, 1e-300))
               for p, w, m in zip(fit["params"], want_params, moved)]
    return {"rate": fit["rate"],
            "first_loss_gap": float(gaps[0]) if whole else None,
            "loss_curve_gap": float(np.median(gaps)) if whole else None,
            "mean_curve_gap": float(gaps.mean()) if whole else None,
            "widest_loss_gap": float(gaps.max()) if whole else None,
            "param_change_gap": max(by_leaf[:-1]),
            "param_change_gap_by_leaf": by_leaf,
            "reference_moved_by_leaf": moved,
            "first_loss": float(losses[0]) if whole else None,
            "last_loss": float(losses[-1]) if whole else None,
            "first_loss_ref": float(want[0]), "last_loss_ref": float(want[-1]),
            "trained": float(losses[-1] / losses[0]) if whole else None}


def compare(s, at: dict, fit: dict, precision=CONFIGURED) -> dict:
    """:func:`window_gaps` and :func:`fit_gaps` of one fit."""
    return {**window_gaps(s, at, precision), **fit_gaps(s, at, fit)}


def check(ctx, s, result, counters):
    at = start_of(s)
    lowest = min(fit["rate"] for _, fit in s.timed)
    last = [fit for _, fit in s.timed if fit["rate"] == lowest][-1]
    cmp = compare(s, at, last)
    print(json.dumps({"phase": "compared", "start_off_rule": at["start_off_rule"],
                      "start_gap": at["start_gap"], **cmp}), flush=True)
    return verdicts(ctx, s, at, cmp, counters)


def verdicts(ctx, s, at: dict, cmp: dict, counters: dict) -> list:
    """The cell's own checks of one fit's :func:`compare` and of the
    window's fits and counters, each a value beside its limit."""
    limits = ctx.size("limits")
    fits = len(s.timed)

    def differs(fit, first):
        return (not np.array_equal(fit["losses"], first["losses"])
                or any(not np.array_equal(a, b)
                       for a, b in zip(fit["params"], first["params"])))

    apart = sum(1 for which, fit in s.timed if differs(fit, s.first[which]))
    steps, counted = counters.get("mlp.steps"), counters.get("mlp.fits", 0)
    policy_steps = counters.get("mlp.policy_steps")
    shape = "-".join(str(d) for d in s.layers)
    of = (f"last timed fit at the lowest rate ({cmp['rate']}; {s.rows} rows, {shape}, "
          f"{s.precision}, batch {s.batch}, {s.steps} steps)")
    there = "step 0's window at the start, the step's own function outside the loop"
    return [
        {"what": "the reference's start off the configuration's rule: the widest of "
                 "|std(W_l) / sqrt(2 / d_{l-1}) - 1| and |b_l|",
         "value": at["start_off_rule"], "limit": 0.05},
        {"what": "start_gap: the program's start (start_params) against the "
                 "reference's own draw, |p - p_ref|_F / |p_ref|_F an array, the "
                 "largest",
         "value": at["start_gap"], "limit": 1e-6},
        {"what": f"{of}: first_loss_gap, |loss(0) - loss_ref(0)| of its curve "
                 f"({cmp['first_loss']} and {cmp['first_loss_ref']})",
         "value": cmp["first_loss_gap"], "limit": limits["first_loss_gap"]},
        {"what": f"{of}: loss_curve_gap, the median over its {s.steps} steps of "
                 "|loss(t) - loss_ref(t)|, the reference at its rate from its own "
                 f"start over the same windows (the mean {cmp['mean_curve_gap']}, "
                 f"the widest {cmp['widest_loss_gap']}; last losses "
                 f"{cmp['last_loss']} and {cmp['last_loss_ref']})",
         "value": cmp["loss_curve_gap"], "limit": limits["loss_curve_gap"]},
        {"what": f"{of}: param_change_gap, |p - p_ref|_F / |p_ref - p_start|_F an "
                 "array of the model it returned, the largest but for the output "
                 f"layer's bias, the last ({cmp['param_change_gap_by_leaf']})",
         "value": cmp["param_change_gap"], "limit": limits["param_change_gap"]},
        {"what": f"{of}: trained, its last step's loss over its first's "
                 f"({cmp['last_loss']} / {cmp['first_loss']})",
         "value": cmp["trained"], "limit": limits["trained"]},
        {"what": f"start_loss_gap ({there}): the root mean square over the rows "
                 "of a row's loss less the reference's (mean loss "
                 f"{cmp['loss_ref']}, the means {cmp['mean_loss_gap']} apart)",
         "value": cmp["start_loss_gap"], "limit": limits["start_loss_gap"]},
        {"what": f"grad_gap ({there}): against the reference's written-out "
                 "backward pass, |g - g_ref|_F / |g_ref|_F a layer, the largest "
                 f"({cmp['grad_gap_by_layer']})",
         "value": cmp["grad_gap"], "limit": limits["grad_gap"]},
        {"what": f"refit_gap: timed fits ({fits}) that differ in any bit (a "
                 "parameter, a step's loss) from set-up's fit of the same rate",
         "value": apart, "limit": 0},
        {"what": "table bytes uploaded inside the window (mlp.table_h2d_bytes)",
         "value": counters.get("mlp.table_h2d_bytes"), "limit": 0},
        {"what": f"steps the program counted, off {s.steps} a timed fit "
                 f"(mlp.steps {steps}, mlp.fits {counted})",
         "value": None if steps is None else
         abs(steps - s.steps * counted) + abs(counted - fits),
         "limit": 0},
        {"what": "steps not run under the configuration's policy (mlp.steps "
                 f"{steps} less mlp.policy_steps {policy_steps})",
         "value": None if steps is None or policy_steps is None
         else steps - policy_steps,
         "limit": 0},
    ]
