"""The controls: each configuration computed one precision below the one
it states has to FAIL the comparison that decides ``correct``, at the
limits the cells' files hold, while the sound path passes. At a size a
test run can hold; the readings at the cells' own sizes, on the chip,
are in PERF.md.

- chain-a9a (float32): the program's own lower-precision path,
  ``pipeline_fusion.precision_scope("mixed_inference")`` (bfloat16
  compute), serves as the control.
- lr-a9a (float32): the reference itself, replaying the same fit at
  bfloat16 features, coefficient and multipliers with float32
  accumulation. (The program's own ``precision`` policy trains with
  momentum SGD, another update rule, so it cannot stand in.)
"""

import json
import os

import numpy as np
import pytest

from benchmark import datagen
from benchmark.drivers import chain_model
from benchmark.reference import chain as chain_ref
from benchmark.reference import linear as linear_ref

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _limits(cell):
    with open(os.path.join(BENCH, "workloads", f"{cell}.json")) as f:
        return json.load(f)["limits"]


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_chain_in_bfloat16_fails_and_in_float32_passes(seed):
    from flinkml_tpu import pipeline_fusion
    from flinkml_tpu.table import Table

    limit = _limits("chain-a9a.transform")["raw_max_abs_err"]
    md = datagen.chain_model_data(seed, 123)
    model = chain_model.build(md)
    x = datagen.normal_matrix(seed, datagen.TAG_FEATURES, 4096, 123)

    def outputs():
        (out,) = model.transform(Table({"features": x}))
        return (np.asarray(out.column("prediction")),
                np.asarray(out.column("rawPrediction")))

    sound = chain_ref.compare(md, x, *outputs())
    with pipeline_fusion.precision_scope("mixed_inference"):
        control = chain_ref.compare(md, x, *outputs())
    assert sound["raw_max_abs_err"] <= limit and sound["pred_mismatch_away"] == 0
    assert control["raw_max_abs_err"] > 3 * limit


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_lr_in_bfloat16_fails_and_in_float32_passes(seed):
    """The cell's own comparison at a test's size: 16 windows of 512
    rows, four times round, through ``LogisticRegression().fit``."""
    from flinkml_tpu.models import LogisticRegression
    from flinkml_tpu.table import Table

    limit = _limits("lr-a9a.fit")["coef_gap"]
    n, batch, steps, rate = 8192, 512, 64, 0.5
    x = datagen.normal_matrix(seed, datagen.TAG_FEATURES, n, 123)
    y = datagen.planted_labels(seed, x)
    order = linear_ref.seeded_order(seed % (1 << 31), n)
    want = linear_ref.minibatch_sgd(x, y, steps, rate, batch, order)
    est = (LogisticRegression().set_global_batch_size(batch).set_max_iter(steps)
           .set_learning_rate(rate).set_tol(0.0).set_seed(seed % (1 << 31)))
    got = np.asarray(est.fit(Table({"features": x, "label": y})).coefficient, np.float64)
    control = linear_ref.minibatch_sgd(x, y, steps, rate, batch, order,
                                       round_to=linear_ref.to_bfloat16)
    assert np.max(np.abs(got - want)) <= limit
    assert np.max(np.abs(control - want)) > 3 * limit
