"""The perceptrons' fit of a ``Table`` (``models/_mlp_table.py``) against
the benchmark's plain reference (``benchmark/reference/mlp.py``:
``jax.numpy`` float32 at ``highest``, the backward pass written out), at
24-40-32-16-5 on the CPU: what ``mlp-mnist8m.fit``'s ``check`` compares on
the chip at 784-2500-2000-1500-1000-500-10."""

import functools
import tracemalloc
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import mlp as driver
from benchmark.reference import mlp as reference
from flinkml_tpu.models import MLPClassifier, MLPRegressor, _mlp_table
from flinkml_tpu.parallel import DeviceMesh
from flinkml_tpu.precision import MIXED, PrecisionValidationError
from flinkml_tpu.table import Table
from flinkml_tpu.utils.metrics import metrics

LAYERS = [24, 40, 32, 16, 5]
ROWS, BATCH, STEPS, SEED, RATE = 2048, 256, 30, 3, 1e-3
#: ``mixed`` against the float32 reference by the cell's own comparisons
#: (``benchmark/drivers/mlp.py``): the limit, and the planted faults that
#: have to read over it (``driver.FAULTS``: operands cut to four bits, half
#: of every window left out of the loop's step, one array never updated,
#: another seed's start). Each limit is at least 3 times the sound reading
#: and at most a third of every named fault's. Read here, PR 52 (sound;
#: four_bits; half_window; frozen_leaf; other_start): start_loss_gap
#: 4.2e-3; 6.2e-2; the last three as sound (outside the loop). grad_gap
#: 6.1e-3; 9.7e-2; as sound. first_loss_gap 3.3e-4; 7.4e-3; 5.9e-2; as
#: sound; 1.4e-1. loss_curve_gap 1.6e-4; 2.3e-3; 1.4e-2; 9.9e-4; 2.2e-2.
#: param_change_gap 1.2e-2; 8.8e-2; 1.08; 1 (an array left where it
#: started); 30.
LIMITS = {"start_loss_gap": (1.5e-2, ["four_bits"]),
          "grad_gap": (2.5e-2, ["four_bits"]),
          "first_loss_gap": (1.5e-3, ["four_bits", "half_window", "other_start"]),
          "loss_curve_gap": (6e-4, ["four_bits", "half_window", "other_start"]),
          "param_change_gap": (4e-2, ["half_window", "frozen_leaf", "other_start"])}


def _data(rows=ROWS, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, LAYERS[0])).astype(np.float32)
    return x, rng.integers(0, LAYERS[-1], size=rows).astype(np.float64)


def _mesh(devices: int) -> DeviceMesh:
    return DeviceMesh({DeviceMesh.DATA_AXIS: devices})


def _classifier(precision=None, devices=1, steps=STEPS, batch=BATCH, seed=SEED,
                rate=RATE, layers=LAYERS):
    return (MLPClassifier(mesh=_mesh(devices), precision=precision)
            .set_layers(layers).set_global_batch_size(batch).set_max_iter(steps)
            .set_tol(0.0).set_learning_rate(rate).set_seed(seed))


@functools.lru_cache(maxsize=None)
def _gaps(precision, fault=None):
    """The cell's comparisons, here: the driver's own functions over a
    set-up of this file's sizes (the fit through ``Estimator.fit`` on the
    default mesh, the suite's eight devices: whole windows, so the rows a
    step reads are one device's)."""
    x, y = _data()
    s = types.SimpleNamespace(
        layers=LAYERS, batch=BATCH, steps=STEPS, precision=precision, rows=ROWS,
        seed=SEED, sweep=[RATE], x=x, y=y.astype(np.int32),
        table=Table({driver.FEATURES: x, driver.LABEL: y}))
    at = driver.start_of(s)
    with driver.planted(fault):
        return {"start_gap": at["start_gap"], **driver.window_gaps(s, at),
                **driver.fit_gaps(s, at, driver.public_fit(s, RATE))}


@pytest.mark.parametrize("what", sorted(LIMITS))
def test_mixed_is_the_reference_within_its_limits(what):
    assert 3 * _gaps("mixed")[what] <= LIMITS[what][0]


@pytest.mark.parametrize("fault,what", [
    (fault, what) for what in sorted(LIMITS) for fault in LIMITS[what][1]])
def test_a_planted_fault_reads_over_the_limit(fault, what):
    assert sorted(driver.FAULTS) == ["four_bits", "frozen_leaf", "half_window",
                                     "other_start"]
    assert _gaps("mixed", fault)[what] >= 3 * LIMITS[what][0]


@pytest.mark.parametrize("fault", ["half_window", "frozen_leaf"])
def test_a_fault_of_the_loops_step_is_unseen_outside_the_loop(fault):
    """Why the cell compares what the timed fit returned: the step's own
    function, run outside the loop, reads what the sound program reads."""
    for what in ("start_loss_gap", "grad_gap"):
        assert _gaps("mixed", fault)[what] == _gaps("mixed")[what]
    # and the program is itself again after the block
    x, y = _data()
    again = _classifier("mixed", devices=8).fit(Table({"features": x, "label": y}))
    assert abs(again.loss_history[0] - _gaps("mixed")["first_loss_ref"]) < 1.5e-3
    assert _gaps("mixed", fault)["first_loss"] != _gaps("mixed")["first_loss"] \
        or fault == "frozen_leaf"


@pytest.mark.parametrize("what", sorted(LIMITS))
def test_no_policy_is_the_reference_at_float32s_own_tolerance(what):
    assert _gaps(None)[what] <= (1e-5 if what == "param_change_gap" else 2e-6)


def test_the_programs_start_is_the_references_own_draw():
    assert _gaps(None)["start_gap"] <= 1e-6
    start = reference.start(LAYERS, SEED)
    for w in start[0::2]:
        assert abs(float(w.std()) / np.sqrt(2.0 / w.shape[0]) - 1.0) < 0.2
    # no two layers share a draw
    assert not np.array_equal(start[2][:16, :16], start[4][:16, :16])
    assert all(not b.any() for b in start[1::2])
    other = reference.start(LAYERS, SEED + 1)
    assert max(reference.relative_gaps(other[0::2], start[0::2])) > 1.0


def test_the_reference_blocks_add_up_to_the_whole():
    x, y = _data(512)
    start = [np.asarray(a) for a in _mlp_table.start_params(LAYERS, SEED, _mesh(1))]
    whole = reference.loss_and_gradients(start, x, y)
    blocks = reference.loss_and_gradients(start, x, y, block=100)
    assert abs(float(whole[0]) - float(blocks[0])) < 1e-6
    assert max(reference.relative_gaps(blocks[1], whole[1])) < 1e-5


def test_the_written_out_backward_pass_is_autodiffs():
    """The program's and the reference's, each against ``jax.grad`` of its
    own forward pass."""
    x, y = _data(256)
    params = _mlp_table.init_params(LAYERS, jax.random.PRNGKey(1))
    w = jnp.ones(256, jnp.float32)
    dot = _mlp_table.product_of(None)

    def loss(params):
        return jnp.sum(_mlp_table.loss_and_gradients(
            params, jnp.asarray(x), jnp.asarray(y, jnp.int32), w, True, dot)[0])

    _, grads = _mlp_table.loss_and_gradients(
        params, jnp.asarray(x), jnp.asarray(y, jnp.int32), w, True, dot)
    assert max(reference.relative_gaps(grads, jax.grad(loss)(params))) < 1e-5

    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: -jnp.mean(jax.nn.log_softmax(
            reference.forward(p, jnp.asarray(x))[1])[jnp.arange(256), y.astype(int)]))(
                params)
    got = reference.loss_and_gradients(params, x, y)[1]
    assert max(reference.relative_gaps(got, want)) < 1e-5


def test_the_regressors_gradient_is_autodiffs_and_it_learns():
    rng = np.random.default_rng(5)
    x = rng.uniform(-1, 1, size=(512, 3)).astype(np.float32)
    y = np.sin(2 * x[:, 0]) + x[:, 1] * x[:, 2]
    params = _mlp_table.init_params([3, 16, 1], jax.random.PRNGKey(2))
    w = jnp.ones(512, jnp.float32)
    dot = _mlp_table.product_of(None)
    args = (jnp.asarray(x), jnp.asarray(y, jnp.float32), w, False, dot)
    _, grads = _mlp_table.loss_and_gradients(params, *args)
    auto = jax.grad(lambda p: jnp.sum(_mlp_table.loss_and_gradients(p, *args)[0]))(params)
    assert max(reference.relative_gaps(grads, auto)) < 1e-5
    model = (MLPRegressor(mesh=_mesh(1)).set_layers([3, 16, 1]).set_max_iter(300)
             .set_global_batch_size(128).set_learning_rate(0.01).set_tol(0.0)
             .set_seed(0).fit(Table({"features": x, "label": y})))
    assert model.loss_history.shape == (300,)
    assert model.loss_history[-1] < 0.2 * model.loss_history[0]


@pytest.mark.parametrize("rows,batch", [(2048, 256), (1000, 256), (100, 256), (257, 64)])
def test_windows_follow_the_seeded_order_and_wrap(rows, batch):
    """Step ``t`` of the program reads what the reference says it reads:
    the loss of every step from the SAME parameters (a rate of 1e-30 moves
    none) names the window, and past the last window the first comes
    again."""
    x, y = _data(rows)
    windows = -(-rows // min(batch, rows))
    steps = windows + 2
    est = _classifier(None, steps=steps, batch=batch, rate=1e-30)
    model = est.fit(Table({"features": x, "label": y}))
    start = [np.asarray(a) for a in _mlp_table.start_params(LAYERS, SEED, est.mesh)]
    order = reference.seeded_order(SEED, rows)
    want = [float(reference.loss_and_gradients(
        start, x[reference.step_rows(order, batch, t)],
        y[reference.step_rows(order, batch, t)])[0]) for t in range(steps)]
    np.testing.assert_allclose(model.loss_history, want, rtol=2e-6)
    assert model.loss_history[windows] == model.loss_history[0]
    last = reference.step_rows(order, batch, windows - 1)
    assert last[-1] == order[-1] and last.shape[0] == min(batch, rows)


@pytest.mark.parametrize("rows", [2048, 2043, 1001])
def test_the_dealt_order_places_every_row_once(rows):
    for p, local_bs in [(1, 256), (4, 64), (8, 32), (8, 300)]:
        order = _mlp_table.dealt_order(rows, p, local_bs, SEED)
        assert sorted(order.tolist()) == list(range(rows))
    # whole windows: every shard's k-th places hold window k, dealt in turn
    if rows == 2048:
        flat = reference.seeded_order(SEED, rows)
        order = _mlp_table.dealt_order(rows, 4, 64, SEED).reshape(4, -1)
        for k in range(rows // 256):
            np.testing.assert_array_equal(
                order[:, 64 * k:64 * (k + 1)].reshape(-1), flat[256 * k:256 * (k + 1)])


@pytest.mark.parametrize("devices", [4, 8])
def test_padded_rows_weigh_nothing(devices):
    """2,043 rows pad to the mesh; the zero rows' weight is 0, so a step's
    loss is the mean over its REAL rows: every window's loss at the start
    is the reference's over the rows the dealt order puts there."""
    rows = 2043
    x, y = _data(rows)
    table = Table({"features": x, "label": y})
    est = _classifier(None, devices=devices, steps=9, rate=1e-30)
    model = est.fit(table)
    start = [np.asarray(a) for a in _mlp_table.start_params(LAYERS, SEED, est.mesh)]
    n_local = -(-rows // devices)
    local_bs = min(-(-BATCH // devices), n_local)
    order = np.full(devices * n_local, -1)
    order[:rows] = _mlp_table.dealt_order(rows, devices, local_bs, SEED)
    order = order.reshape(devices, n_local)
    windows = -(-n_local // local_bs)
    for t in range(9):
        at = min((t % windows) * local_bs, n_local - local_bs)
        read = order[:, at:at + local_bs].reshape(-1)
        read = read[read >= 0]
        want = float(reference.loss_and_gradients(start, x[read], y[read])[0])
        assert abs(model.loss_history[t] - want) <= 2e-6 * want


@pytest.mark.parametrize("precision", [None, "mixed"])
def test_the_same_rows_seed_and_rate_give_the_same_bits(precision):
    x, y = _data()
    one = _classifier(precision).fit(Table({"features": x, "label": y}))
    two = _classifier(precision).fit(Table({"features": x.copy(), "label": y.copy()}))
    np.testing.assert_array_equal(one.loss_history, two.loss_history)
    for a, b in zip(one._weights, two._weights):
        np.testing.assert_array_equal(a, b)
    other = _classifier(precision, seed=SEED + 1).fit(Table({"features": x, "label": y}))
    assert not np.array_equal(one.loss_history, other.loss_history)


def _uploaded() -> float:
    return metrics.group("mlp").snapshot()["counters"].get("table_h2d_bytes", 0.0)


def test_a_second_fit_uploads_nothing_of_the_table_and_a_new_table_its_bytes():
    x, y = _data()
    table = Table({"features": x, "label": y})
    before = _uploaded()
    first = _classifier("mixed").fit(table)
    placed = _uploaded() - before
    assert placed == x.nbytes + 4 * ROWS          # float32 rows, int32 labels
    again = _classifier("mixed", rate=3e-4).fit(table)
    same = _classifier("mixed").fit(table)
    assert _uploaded() - before == placed          # a sweep of the rate: a hit
    np.testing.assert_array_equal(first.loss_history, same.loss_history)
    assert not np.array_equal(first.loss_history, again.loss_history)
    _classifier("mixed").fit(Table({"features": x, "label": y}))
    assert _uploaded() - before == 2 * placed
    # one device holds the seeded order whatever the batch; another seed is
    # another order
    halves = _classifier("mixed", batch=128, steps=2 * STEPS).fit(table)
    assert _uploaded() - before == 2 * placed
    head = reference.seeded_order(SEED, ROWS)[:128]
    start = [np.asarray(a) for a in _mlp_table.start_params(LAYERS, SEED, _mesh(1))]
    want = float(reference.loss_and_gradients(start, x[head], y[head])[0])
    assert abs(halves.loss_history[0] - want) < 2e-3   # its OWN batch, not the kept fit's
    assert abs(first.loss_history[0] - want) > 1e-5
    _classifier("mixed", seed=SEED + 1).fit(table)
    assert _uploaded() - before == 3 * placed


def test_no_float64_copy_of_the_column_is_made():
    x, y = _data(16_384)
    table = Table({"features": x, "label": y.astype(np.int32)})
    est = _classifier(None, steps=2)
    est.fit(Table({"features": x[:512], "label": y[:512]}))   # compiled, warmed
    tracemalloc.start()
    est.fit(table)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    # the staging buffers are the column's size here; float64 would be twice
    assert peak < 1.6 * x.nbytes, (peak, x.nbytes)


@pytest.mark.parametrize("devices", [4, 8])
def test_the_loss_curve_is_one_devices_whatever_the_mesh(devices):
    """2,048 rows in whole windows of 256: every step reads the same rows
    on 1, 4 and 8 devices (``dealt_order``), joined by the real psum."""
    x, y = _data()
    table = Table({"features": x, "label": y})
    one = _classifier(None).fit(table)
    many = _classifier(None, devices=devices).fit(table)
    np.testing.assert_allclose(many.loss_history, one.loss_history, rtol=2e-5)
    for a, b in zip(many._weights, one._weights):
        np.testing.assert_allclose(a, b, atol=2e-4)


def test_mixed_is_accepted_and_its_step_passes_the_precision_check():
    est = MLPClassifier(precision="mixed")
    assert est.precision is MIXED
    step = _mlp_table.make_step(True, 64, "data", _mlp_table.product_of(MIXED))
    _mlp_table._check_policy(MIXED, step, LAYERS, 64, jnp.int32, 4, "data")
    # the products take bfloat16 operands and sum in float32, on a CPU too
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    tree = tuple(s for a, b in zip(LAYERS, LAYERS[1:]) for s in (f32(a, b), f32(b)))
    closed = jax.make_jaxpr(step, axis_env=[("data", 4)])(
        tree, tree, tree, jax.ShapeDtypeStruct((), jnp.int32), f32(256, LAYERS[0]),
        jax.ShapeDtypeStruct((256,), jnp.int32), f32(256), f32())

    def dots(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from dots(sub)

    found = list(dots(closed.jaxpr))
    assert len(found) == 3 * (len(LAYERS) - 1) - 1
    for eqn in found:
        assert [v.aval.dtype for v in eqn.invars] == [jnp.bfloat16, jnp.bfloat16]
        assert eqn.params["preferred_element_type"] == jnp.float32
        assert eqn.outvars[0].aval.dtype == jnp.float32


def test_a_step_that_sums_in_bfloat16_is_refused_before_any_compile():
    def narrow(a, b, contract=(1, 0)):
        return jax.lax.dot_general(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            (((contract[0],), (contract[1],)), ((), ())))

    step = _mlp_table.make_step(True, 64, "data", narrow)
    with pytest.raises(PrecisionValidationError, match="FML601"):
        _mlp_table._check_policy(MIXED, step, LAYERS, 64, jnp.int32, 4, "data")


def test_what_the_fits_refuse_and_what_the_model_keeps(tmp_path):
    x, y = _data(300)
    table = Table({"features": x, "label": y})
    with pytest.raises(ValueError, match="class ids"):
        _classifier().fit(Table({"features": x, "label": y + 0.5}))
    with pytest.raises(ValueError, match="class ids"):
        _classifier().fit(Table({"features": x, "label": y + 1}))
    with pytest.raises(ValueError, match="feature dim"):
        _classifier(layers=[7, 5]).fit(table)
    with pytest.raises(ValueError, match="table fit"):
        _classifier("mixed").fit(iter([table]))
    # tol stops the loop, and the curve holds the steps that ran
    stopped = _classifier(steps=40).set_tol(10.0).fit(table)
    assert stopped.loss_history.shape == (2,)
    model = _classifier("mixed", steps=5).fit(table)
    assert model.loss_history.shape == (5,) and model.loss_history.dtype == np.float32
    model.save(str(tmp_path / "mlp"))
    loaded = type(model).load(str(tmp_path / "mlp"))
    np.testing.assert_array_equal(loaded.loss_history, model.loss_history)
    np.testing.assert_array_equal(
        loaded.transform(table)[0]["prediction"], model.transform(table)[0]["prediction"])


def test_spans_and_counters_of_a_fit():
    x, y = _data(512)
    table = Table({"features": x, "label": y})

    def snapshot():
        return ({k: v for k, v in metrics.group("span").snapshot()["counters"].items()},
                dict(metrics.group("mlp").snapshot()["counters"]))

    _classifier("mixed", steps=4).fit(table)
    spans0, counts0 = snapshot()
    _classifier("mixed", steps=4).fit(table)
    _classifier(None, steps=4).fit(table)
    spans, counts = snapshot()
    for name in ("fit", "mlp.place", "mlp.dispatch", "mlp.readback"):
        assert spans[f"{name}.calls"] - spans0.get(f"{name}.calls", 0) == 2
    assert counts["fits"] - counts0["fits"] == 2
    assert counts["steps"] - counts0["steps"] == 8
    assert counts["policy_steps"] - counts0["policy_steps"] == 4
    assert counts["rows"] - counts0["rows"] == 1024
    assert counts["table_h2d_bytes"] == counts0["table_h2d_bytes"]
