"""The factorization machines' fit of a ``CsrColumn`` (PR 36,
``models/_fm_sparse.py``) against ``benchmark/reference/fm.py`` (NumPy
float64, Rendle's equations over cells, Adam, the windows of the seeded
order):

- margin and gradient of one batch against the reference, and against
  ``fm._fm_margin`` (the dense fit's) on the same rows densified;
- ``ops.sparse.block_lookup`` / ``block_accumulate`` with a payload axis:
  the rows ``P[idx]`` bit for bit and ``.at[idx].add`` (bit for bit where
  the sums are exact, to float32 rounding elsewhere), blocks of 128
  included, and the step with overlapping blocks against the step with
  no plan;
- whole fits (rows of one width, field-blocked ragged rows, hashed ragged
  rows; weights; classifier and regressor) within a stated float32
  tolerance that the same fit with its looked-up rows rounded to
  bfloat16 fails;
- the second fit of a ``Table`` uploads nothing and is bit-equal; a sweep
  over rate and ``reg`` lowers nothing; no ``SparseVector`` is built, by
  the fit or by the model's ``transform``;
- the span tree's self seconds add up to ``fit``'s; four data shards
  joined by the real ``psum`` equal one;
- ``lr-criteo``'s blocked step lowers to the text it had before the
  payload axis;
- the same fits with the blocked slots in ``kernels.payload_blocks``' two
  kernels (PR 53: a TPU's, here interpreted), one device and four;
  overlapping blocks; and what the kernels do not take (a batch that is
  not whole tiles, a block too long for fast memory, the control's one
  pass) is the fit as it was, to the bit;
- the hand-over (PR 56): the model holds the float32 buffers the fit's one
  ``jax.device_get`` returned, laid ``w [dim]`` and ``V [dim, k]`` by the
  device to the bit of the host's turn of the raw table; every margin
  path scores a float32 model as its float64 copy, to the bit; ``save``,
  ``load`` and ``set_model_data`` keep the floating dtype they meet.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import fm as reference
from flinkml_tpu.models import FMClassifier, FMRegressor, _fm_sparse, _linear_sgd, fm
from flinkml_tpu.ops import sparse
from flinkml_tpu.parallel import DeviceMesh
from flinkml_tpu.table import CsrColumn, Table
from flinkml_tpu.utils.metrics import metrics

K = 6
#: Widest parameter gap to float64 Adam that a float32 fit of these rows
#: may show after STEPS steps at RATE. Adam divides the gradient by its
#: own size plus 1e-8, so where a gradient is about 1e-8 (a few of these
#: small tables' parameters every step) float32's rounding of it, 1e-11,
#: moves the update by RATE * 1e-11 / 1e-8: 2e-6 to 2e-5 as read here over
#: the cases (the cell's gradients are 1e4 times further from their
#: rounding, benchmark/tests/chip_controls_fm.py). Looked-up rows rounded
#: to bfloat16 (what one MXU pass makes of them) read 3e-3 to 6e-3.
TOL, STEPS, RATE, REG, BATCH = 5e-5, 12, 0.01, 1e-3, 256


def _counters(group):
    return dict(metrics.group(group).snapshot()["counters"])


@contextlib.contextmanager
def _delta(group):
    before, out = _counters(group), {}
    yield out
    for k, v in _counters(group).items():
        if v != before.get(k, 0.0):
            out[k] = v - before.get(k, 0.0)


def _one_device():
    return DeviceMesh(devices=jax.devices()[:1])


def _field_rows(rng, rows, fields=5, stratum=300, keep=1.0):
    """``(indptr, indices, values, dim, ell_idx, ell_val)``: one cell a
    field, field ``f`` on columns ``[f * stratum, (f + 1) * stratum)``;
    with ``keep < 1`` cells are dropped (ragged rows that keep to
    fields; the first row stays whole). The ELL pair pads with value 0."""
    dim = fields * stratum
    idx = (rng.integers(0, stratum, (rows, fields))
           + np.arange(fields) * stratum).astype(np.int32)
    val = rng.standard_normal((rows, fields)).astype(np.float32)
    kept = rng.random((rows, fields)) < keep
    kept[0] = True
    kept[:, 0] = True  # no empty row
    return _csr(idx, val, kept, dim)


def _hashed_rows(rng, rows, dim=1500, widest=7):
    """Ragged rows hashed over all of ``dim``: no field, no plan."""
    idx = np.sort(rng.integers(0, dim, (rows, widest)), axis=1).astype(np.int32)
    idx += np.arange(widest, dtype=np.int32)  # distinct within a row
    val = rng.standard_normal((rows, widest)).astype(np.float32)
    kept = np.arange(widest) < rng.integers(1, widest + 1, rows)[:, None]
    kept[0] = True
    return _csr(idx, val, kept, dim + widest)


def _csr(idx, val, kept, dim):
    nnz = kept.sum(axis=1)
    indptr = np.concatenate([[0], np.cumsum(nnz)]).astype(np.int64)
    ell_val = np.where(kept, val, 0).astype(np.float32)
    return indptr, idx[kept], val[kept], dim, idx, ell_val


def _table(rows_of, y, w=None):
    indptr, indices, values, dim = rows_of[:4]
    cols = {"features": CsrColumn(indptr, indices, values, dim), "label": y}
    if w is not None:
        cols["weight"] = w
    return Table(cols)


def _estimator(cls=FMClassifier, mesh=None, seed=3, rate=RATE, reg=REG,
               steps=STEPS, batch=BATCH, weight=False):
    est = (cls().set_factor_size(K).set_max_iter(steps)
           .set_global_batch_size(batch).set_learning_rate(rate).set_reg(reg)
           .set_tol(0.0).set_seed(seed))
    if weight:
        est.set_weight_col("weight")
    est.mesh = mesh or _one_device()
    return est


def _want(rows_of, y, seed=3, w=None, logistic=True, shards=1, **kw):
    dim, idx, val = rows_of[3:]
    start = np.asarray(fm.start_factors(dim, K, seed))
    return reference.adam_fit(
        idx, val, dim, y, start, kw.get("steps", STEPS), kw.get("rate", RATE),
        kw.get("reg", REG), kw.get("batch", BATCH),
        reference.seeded_order(seed, idx.shape[0]), weights=w,
        logistic=logistic, shards=shards, threads=2)


def _gap(model, want):
    w0, w, v, _ = want
    return max(abs(model._w0 - w0), np.abs(model._w - w).max(),
               np.abs(model._v - v).max())


# -- the equations ------------------------------------------------------------

def test_margin_is_the_dense_fits_and_the_references():
    rng = np.random.default_rng(0)
    rows_of = _field_rows(rng, 200)
    dim, idx, val = rows_of[3:]
    w0 = 0.3
    w = rng.standard_normal(dim)
    v = 0.1 * rng.standard_normal((dim, K))
    got, s, _ = reference.margin(w0, w, v, idx, val.astype(np.float64))
    dense = reference.densified(idx, val, dim)
    np.testing.assert_allclose(got, reference.dense_margin(w0, w, v, dense),
                               rtol=1e-12, atol=1e-12)
    with jax.enable_x64(True):
        program = np.asarray(fm._fm_margin(
            (jnp.asarray([w0]), jnp.asarray(w), jnp.asarray(v)), jnp.asarray(dense)))
    np.testing.assert_allclose(got, program, rtol=1e-11, atol=1e-11)
    np.testing.assert_allclose(s.T, dense @ v, rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("logistic", [True, False])
def test_one_steps_gradient_is_the_references_and_autodiffs(logistic):
    """The sparse step's gradient, read off one Adam step from zero
    moments (``m = 0.1 g``), against the reference's closed form and
    against ``jax.grad`` of the dense fit's loss on the rows densified."""
    rng = np.random.default_rng(1)
    rows_of = _field_rows(rng, BATCH)
    dim, idx, val = rows_of[3:]
    y = ((rng.random(BATCH) < 0.4).astype(np.float32) if logistic
         else rng.standard_normal(BATCH).astype(np.float32))
    wt = (rng.random(BATCH) + 0.5).astype(np.float32)
    w0 = np.float32(0.2)
    table = (0.1 * rng.standard_normal((K + 1, _fm_sparse.padded_dim(dim)))
             ).astype(np.float32)
    table[:, dim:] = 0
    w, v = table[0, :dim].astype(np.float64), table[1:, :dim].T.astype(np.float64)
    loss, (g0, gw, gv) = reference.loss_and_gradients(
        float(w0), w, v, idx, val, y, wt, REG, logistic)
    # the program's step under the plan of these rows
    with _linear_sgd.gather_pool() as pool:
        plan, starts = sparse.slot_block_plan(idx, dim, 1, pool)
    assert all(plan)
    mesh = _one_device()
    step = _fm_sparse.make_step(logistic, BATCH, "data", plan)
    params = (jnp.asarray([w0]), jnp.asarray(table.reshape(K + 1, -1, 128)))
    zeros = jax.tree.map(jnp.zeros_like, params)
    with jax.enable_x64(False):
        run = jax.jit(jax.shard_map(
            lambda *a: step(params, zeros, zeros, jnp.int32(0), *a,
                            jnp.float32(0.0), jnp.float32(REG)),
            mesh=mesh.mesh, in_specs=(jax.sharding.PartitionSpec(),) * 5,
            out_specs=jax.sharding.PartitionSpec()))
        _, m, _, got_loss = run(idx, val, y, wt, starts)
    got0 = float(m[0][0]) / 0.1
    got = np.asarray(m[1]).reshape(K + 1, -1)[:, :dim].astype(np.float64) / 0.1
    assert float(got_loss) == pytest.approx(loss, rel=2e-6)
    assert got0 == pytest.approx(g0, abs=2e-7)
    scale = max(np.abs(gw).max(), np.abs(gv).max())
    assert np.abs(got[0] - gw).max() < 2e-6 * scale
    assert np.abs(got[1:].T - gv).max() < 2e-6 * scale
    # and the reference against autodiff of the module's own dense loss
    dense = jnp.asarray(reference.densified(idx, val, dim))
    builder = (fm._fm_logistic_loss_builder if logistic
               else fm._fm_squared_loss_builder)()
    with jax.enable_x64(True):
        grads = jax.grad(lambda p: builder(
            p + (jnp.asarray([REG]),), dense, jnp.asarray(y, jnp.float64),
            jnp.asarray(wt, jnp.float64)) / wt.astype(np.float64).sum())(
                (jnp.asarray([float(w0)]), jnp.asarray(w), jnp.asarray(v)))
    np.testing.assert_allclose(float(grads[0][0]), g0, atol=1e-12)
    np.testing.assert_allclose(np.asarray(grads[1]), gw, atol=1e-12)
    np.testing.assert_allclose(np.asarray(grads[2]), gv, atol=1e-12)


# -- the payload axis ---------------------------------------------------------

#: Block lengths from one product row (no product at all) up a long one.
LENGTHS = [128, 256, 1024, 3072, 26_624]


def _payload_cells(length, width=K + 1, slots=3, rows=700, seed=0):
    rng = np.random.default_rng(seed)
    blocks = rng.standard_normal((slots, length, width)).astype(np.float32)
    blocks[0, :4, 0] = [0.0, 1e-30, -3.5e20, np.float32(1) + np.float32(2) ** -23]
    local = rng.integers(0, length, (slots, rows)).astype(np.int32)
    local[0, :4] = [0, 1, 2, 3]
    return blocks, local, rng.standard_normal((slots, rows, width)).astype(np.float32)


@pytest.mark.parametrize("length", LENGTHS)
def test_payload_lookup_is_the_gather_bit_for_bit(length):
    blocks, local, _ = _payload_cells(length)
    got = np.asarray(jax.jit(sparse.block_lookup)(blocks, local))
    want = np.stack([blocks[s][local[s]] for s in range(len(blocks))])
    assert got.dtype == np.float32 and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("length", [256, 26_624])
def test_a_bfloat16_payload_lookup_fails_the_bit_for_bit_test(length):
    blocks, local, _ = _payload_cells(length)
    low = jnp.asarray(blocks).astype(jnp.bfloat16).astype(jnp.float32)
    got = np.asarray(jax.jit(sparse.block_lookup)(low, local))
    want = np.stack([blocks[s][local[s]] for s in range(len(blocks))])
    assert np.mean(got != want) > 0.9


@pytest.mark.parametrize("length", LENGTHS)
def test_payload_accumulate_is_the_scatter_add(length):
    _, local, contrib = _payload_cells(length, rows=3000)
    accumulate = jax.jit(sparse.block_accumulate, static_argnums=2)
    # small integers: every partial sum is exact, so any order of
    # summation gives the scatter-add's bits
    exact = np.rint(4 * contrib).astype(np.float32)
    got = np.asarray(accumulate(local, exact, length))
    want = np.stack([np.asarray(
        jnp.zeros((length, exact.shape[-1]), jnp.float32).at[local[s]].add(exact[s]))
        for s in range(local.shape[0])])
    assert got.shape == want.shape and got.dtype == np.float32
    assert got.tobytes() == want.tobytes()
    # any floats: float32's rounding of the same sums, the same bits twice
    got = np.asarray(accumulate(local, contrib, length))
    wide = np.zeros(got.shape)
    for s in range(local.shape[0]):
        np.add.at(wide[s], local[s], contrib[s].astype(np.float64))
    scatter = np.stack([np.asarray(
        jnp.zeros((length, contrib.shape[-1]), jnp.float32).at[local[s]].add(contrib[s]))
        for s in range(local.shape[0])])
    assert np.abs(got - wide).max() < 4 * max(np.abs(scatter - wide).max(), 2.0 ** -22)
    assert got.tobytes() == np.asarray(accumulate(local, contrib, length)).tobytes()


def test_payload_products_ask_for_the_precision_they_are_given():
    """On the CPU every precision is float32: the request is checked."""
    blocks, local, contrib = _payload_cells(1024)
    for precision, said in ((None, "HIGHEST"), (jax.lax.Precision.DEFAULT, "DEFAULT")):
        kw = {} if precision is None else {"precision": precision}
        texts = (
            jax.jit(lambda b, i: sparse.block_lookup(b, i, **kw)).lower(
                blocks, local).as_text(),
            jax.jit(lambda i, c: sparse.block_accumulate(i, c, 1024, **kw)).lower(
                local, contrib).as_text())
        for text in texts:
            assert text.count("dot_general") == 1
            assert f"precision = [{said}, {said}]" in text


def test_a_payload_products_rows_divide_every_block_length():
    for length in (128, 256, 512, 1024, 2048, 3072, 6144, 13_312, 26_624, 194_560):
        for c in (sparse.lookup_columns(length), sparse.accumulate_columns(length)):
            assert length % c == 0 and 2 <= c <= 128 and c & (c - 1) == 0
    assert sparse.lookup_columns(26_624) == sparse.accumulate_columns(26_624) == 128
    # never 16 for an accumulation (PERF.md section 5, PR 36)
    assert all(sparse.accumulate_columns(128 * k) != 16 for k in range(1, 1521))


def _lr_step_text(monkeypatch, old):
    """The lowered text of ``_linear_sgd``'s blocked step under a mixed
    plan, with this tree's block functions or with ``old``'s."""
    if old:
        monkeypatch.setattr(_linear_sgd, "block_lookup", _block_lookup_before)
        monkeypatch.setattr(_linear_sgd, "block_accumulate", _block_accumulate_before)
    plan = (128, 128, 256, None, 1024, None)
    step = _linear_sgd.make_sparse_step_bucketed("logistic", (16,), "data", 5000,
                                                 plan)
    mesh = _one_device()
    f32, i32 = jnp.float32, jnp.int32
    spec = jax.sharding.PartitionSpec()
    return jax.jit(jax.shard_map(step, mesh=mesh.mesh, in_specs=(spec,) * 10,
                                 out_specs=(spec, spec))).lower(
        jax.ShapeDtypeStruct((5000,), f32), jax.ShapeDtypeStruct((), i32),
        jax.ShapeDtypeStruct((64, 6), i32), jax.ShapeDtypeStruct((64, 6), f32),
        jax.ShapeDtypeStruct((64,), f32), jax.ShapeDtypeStruct((64,), f32),
        jax.ShapeDtypeStruct((6,), i32), jax.ShapeDtypeStruct((), f32),
        jax.ShapeDtypeStruct((), f32), jax.ShapeDtypeStruct((), f32)).as_text()


def _block_one_hots_before(local, k, dtype):
    lanes = jax.nn.one_hot(local % 128, 128, dtype=jnp.bool_)
    if k == 1:
        return None, lanes
    return jax.nn.one_hot(local // 128, k, dtype=dtype), lanes


def _block_lookup_before(blocks, local):
    """``ops.sparse.block_lookup`` as PR 35 had it (a copy)."""
    s, r = blocks.shape
    k = r // 128
    rows_of, lanes = _block_one_hots_before(local, k, blocks.dtype)
    if rows_of is None:
        rows = blocks[:, None, :]
    else:
        rows = jnp.einsum(
            "sbk,skl->sbl", rows_of, blocks.reshape(s, k, 128),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=blocks.dtype)
    return jnp.sum(jnp.where(lanes, rows, 0), axis=-1)


def _block_accumulate_before(local, contrib, length):
    """``ops.sparse.block_accumulate`` as PR 35 had it (a copy)."""
    s, _ = local.shape
    k = length // 128
    rows_of, lanes = _block_one_hots_before(local, k, contrib.dtype)
    spread = jnp.where(lanes, contrib[..., None], 0)
    if rows_of is None:
        return jnp.sum(spread, axis=1)
    out = jnp.einsum(
        "sbk,sbl->skl", rows_of, spread,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=contrib.dtype)
    return out.reshape(s, length)


def test_lr_criteos_blocked_step_is_the_program_it_was(monkeypatch):
    """The payload axis is a branch beside the one-float path: the sparse
    LR step lowers to the text it has with PR 35's two functions."""
    with jax.enable_x64(False):
        now = _lr_step_text(monkeypatch, old=False)
        before = _lr_step_text(monkeypatch, old=True)
    assert "dot_general" in now
    assert now == before


# -- whole fits ---------------------------------------------------------------

def _labels(rng, rows, logistic=True):
    if logistic:
        return (rng.random(rows) < 0.35).astype(np.float32)
    return rng.standard_normal(rows).astype(np.float32)


CASES = {
    "one width": lambda rng: _field_rows(rng, 1500),
    "field-blocked ragged": lambda rng: _field_rows(rng, 1500, keep=0.93),
    "hashed ragged": lambda rng: _hashed_rows(rng, 1500),
}


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("cls", [FMClassifier, FMRegressor])
@pytest.mark.parametrize("weight", [False, True])
def test_fit_is_float64_adam_over_the_seeded_order(case, cls, weight):
    rng = np.random.default_rng(11)
    rows_of = CASES[case](rng)
    logistic = cls is FMClassifier
    y = _labels(rng, 1500, logistic)
    w = (rng.random(1500) + 0.5).astype(np.float32) if weight else None
    with _delta("fm") as counts, _delta("table") as tab:
        model = _estimator(cls, weight=weight).fit(_table(rows_of, y, w))
    want = _want(rows_of, y, w=w, logistic=logistic)
    assert _gap(model, want) <= TOL
    start = np.asarray(fm.start_factors(rows_of[3], K, 3))
    assert np.abs(want[2] - start).max() > 20 * TOL      # the fit moved
    assert counts["steps"] == STEPS and counts["fits"] == 1
    assert counts["cells"] == rows_of[0][-1]
    planned = case != "hashed ragged"
    assert counts.get("blocked_cells", 0.0) == (counts["cells"] if planned else 0.0)
    assert tab.get("csr_rows_materialized", 0.0) == 0.0


@pytest.mark.parametrize("case", ["one width", "field-blocked ragged"])
def test_looked_up_rows_in_bfloat16_fail_the_same_comparison(case, monkeypatch):
    """What one bfloat16 pass of the lookup's product makes of the table
    (on the CPU a precision changes nothing, so the rows are rounded)."""
    rng = np.random.default_rng(11)
    rows_of = CASES[case](rng)
    y = _labels(rng, 1500)
    real = sparse.block_lookup

    def rounded(blocks, local, precision=None):
        low = blocks.astype(jnp.bfloat16).astype(jnp.float32)
        return real(low, local)

    monkeypatch.setattr(sparse, "block_lookup", rounded)
    _fm_sparse._trainer.cache_clear()
    try:
        model = _estimator().fit(_table(rows_of, y))
    finally:
        monkeypatch.undo()
        _fm_sparse._trainer.cache_clear()
    assert _gap(model, _want(rows_of, y)) > 20 * TOL


def test_float32_stays_float32_whatever_x64_says():
    rng = np.random.default_rng(5)
    rows_of = _field_rows(rng, 600)
    y = _labels(rng, 600)
    with jax.enable_x64(True):
        wide = _estimator().fit(_table(rows_of, y))
    with jax.enable_x64(False):
        narrow = _estimator().fit(_table(rows_of, y))
    assert wide._v.tobytes() == narrow._v.tobytes()
    assert wide._w.tobytes() == narrow._w.tobytes()


def test_the_second_fit_of_a_table_uploads_nothing_and_is_bit_equal():
    rng = np.random.default_rng(6)
    rows_of = _field_rows(rng, 900)
    table = _table(rows_of, _labels(rng, 900))
    with _delta("fm") as first, _delta("span") as spans:
        a = _estimator().fit(table)
    assert first["table_uploads"] == 1
    assert first["table_h2d_bytes"] == 900 * (5 * 8 + 4)   # cells and labels
    assert spans["fm.table_to_device.calls"] == 1
    assert spans["hostdata.sparse_pack.calls"] == 1
    with _delta("fm") as second, _delta("span") as spans:
        b = _estimator().fit(table)
    assert "table_uploads" not in second and "table_h2d_bytes" not in second
    assert "fm.table_to_device.calls" not in spans
    assert "hostdata.sparse_pack.calls" not in spans
    assert "mesh.shard_batch.calls" not in spans
    for x, z in ((a._w0, b._w0), (a._w, b._w), (a._v, b._v)):
        assert np.asarray(x).tobytes() == np.asarray(z).tobytes()
    # another seed is another order: placed again, beside the first
    with _delta("fm") as third:
        _estimator(seed=4).fit(table)
    assert third["table_uploads"] == 1


def test_a_sweep_over_rate_and_reg_lowers_nothing():
    rng = np.random.default_rng(7)
    table = _table(_field_rows(rng, 600), _labels(rng, 600))
    _estimator().fit(table)
    lowered = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_, **__: lowered.append(name)
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration" else None)
    models = [_estimator(rate=rate, reg=reg, steps=steps).fit(table)
              for rate, reg, steps in ((0.003, 0.0, STEPS), (0.02, 1e-4, 5))]
    assert lowered == []
    assert not np.array_equal(models[0]._v, models[1]._v)


def test_tol_stops_the_loop_and_zero_runs_every_step():
    rng = np.random.default_rng(8)
    rows_of = _field_rows(rng, 600)
    y = _labels(rng, 600)
    table = _table(rows_of, y)
    with _delta("fm") as counts:
        _estimator(steps=40).set_tol(0.5).fit(table)
    # the first two losses lie within 0.5 of each other: two steps run
    assert counts["steps"] == 2
    want = _want(rows_of, y, steps=2)
    with _delta("fm") as counts:
        model = _estimator(steps=40).set_tol(0.5).fit(table)
    assert _gap(model, want) <= TOL


def test_the_spans_self_seconds_add_up_to_the_fits():
    rng = np.random.default_rng(9)
    rows_of = _field_rows(rng, 900)
    table = _table(rows_of, _labels(rng, 900))
    with _delta("span") as d:
        _estimator().fit(table)
    calls = {k[:-len(".calls")] for k in d if k.endswith(".calls")}
    assert calls == {"fit", "hostdata.ingest", "hostdata.sparse_pack",
                     "fm.table_to_device", "hostdata.shuffle", "hostdata.permute",
                     "hostdata.stage_wait", "mesh.shard_batch", "fm.init",
                     "fm.loop", "fm.dispatch", "fm.readback"}
    own = sum(v for k, v in d.items() if k.endswith(".self_seconds")
              and not k.endswith(".traced_self_seconds"))
    assert own == pytest.approx(d["fit.seconds"], rel=1e-9)
    assert d["fm.table_to_device.bytes"] == 900 * (5 * 8 + 4)
    with _delta("span") as again:
        _estimator().fit(table)
    assert {k[:-len(".calls")] for k in again if k.endswith(".calls")} == {
        "fit", "hostdata.ingest", "fm.init", "fm.loop", "fm.dispatch",
        "fm.readback"}


@pytest.mark.parametrize("case", ["one width", "hashed ragged"])
def test_four_shards_joined_by_the_real_psum_are_the_reference_over_the_whole(case):
    """Four devices each window their own share of the seeded order and
    ``psum`` their gradients: the reference's replay over the same rows."""
    rng = np.random.default_rng(12)
    rows_of = CASES[case](rng)
    y = _labels(rng, 1500)
    w = (rng.random(1500) + 0.5).astype(np.float32)
    mesh = DeviceMesh(devices=jax.devices()[:4])
    model = _estimator(mesh=mesh, weight=True).fit(_table(rows_of, y, w))
    assert _gap(model, _want(rows_of, y, w=w, shards=4)) <= TOL
    # a table the mesh pads: 1,498 rows over four devices
    cut = (rows_of[0][:1499], rows_of[1][:rows_of[0][1498]],
           rows_of[2][:rows_of[0][1498]], rows_of[3], rows_of[4][:1498],
           rows_of[5][:1498])
    model = _estimator(mesh=mesh).fit(_table(cut, y[:1498]))
    assert _gap(model, _want(cut, y[:1498], shards=4)) <= TOL


def test_overlapping_blocks_add_and_agree_with_no_plan():
    """Two slots on ONE block of 128 and a third whose block of 256
    overlaps it: the step under the plan against the step under none."""
    rng = np.random.default_rng(13)
    rows, dim = 512, 1000
    idx = np.stack([rng.integers(0, 100, rows), rng.integers(20, 128, rows),
                    rng.integers(90, 250, rows), rng.integers(0, dim, rows)],
                   axis=1).astype(np.int32)
    val = rng.standard_normal((rows, 4)).astype(np.float32)
    y, wt = _labels(rng, rows), np.ones(rows, np.float32)
    table = (0.1 * rng.standard_normal((K + 1, 1024))).astype(np.float32)
    table[:, dim:] = 0
    params = (jnp.asarray([0.1], jnp.float32), jnp.asarray(table.reshape(K + 1, 8, 128)))
    zeros = jax.tree.map(jnp.zeros_like, params)
    mesh, spec = _one_device(), jax.sharding.PartitionSpec()

    def run(plan, starts):
        step = _fm_sparse.make_step(True, rows, "data", plan)
        with jax.enable_x64(False):
            return jax.jit(jax.shard_map(
                lambda *a: step(params, zeros, zeros, jnp.int32(0), *a,
                                jnp.float32(0.01), jnp.float32(1e-3)),
                mesh=mesh.mesh, in_specs=(spec,) * 5, out_specs=spec))(
                    idx, val, y, wt, starts)

    planned = run((128, 128, 256, None), np.asarray([0, 0, 0, 0], np.int32))
    general = run((), np.zeros(1, np.int32))
    for a, b in zip(jax.tree.leaves(planned[1]), jax.tree.leaves(general[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=2e-8)
    assert float(planned[3]) == pytest.approx(float(general[3]), rel=1e-6)


# -- the walk in fast memory (PR 53) ------------------------------------------

@pytest.fixture
def through_the_kernels(monkeypatch):
    """The fit as a TPU traces it, ``kernels.payload_blocks``' kernels
    interpreted: the gate answers as on a TPU wherever the kernels take
    the step (whole tiles of 128 rows a device; an interpreted kernel's
    values carry no mesh axes, so the ``shard_map`` does not check
    them)."""
    import functools

    from flinkml_tpu.kernels import payload_blocks

    def as_on_a_tpu(dtype, local_bs, slot_plan, payload, precision):
        return (precision == _fm_sparse.LOOKUP_PRECISION
                and payload_blocks.unsupported_reason(
                    dtype, local_bs, _fm_sparse._walk(slot_plan)[0], payload) is None)

    monkeypatch.setattr(_fm_sparse, "_walk_in_fast_memory", as_on_a_tpu)
    monkeypatch.setattr(jax, "shard_map",
                        functools.partial(jax.shard_map, check_vma=False))
    _fm_sparse._trainer.cache_clear()
    yield
    _fm_sparse._trainer.cache_clear()


@pytest.mark.parametrize("case,cls,shards", [
    ("one width", FMClassifier, 1), ("field-blocked ragged", FMClassifier, 1),
    ("one width", FMRegressor, 1), ("one width", FMClassifier, 4)])
def test_a_fit_through_the_kernels_is_float64_adam_too(
        case, cls, shards, through_the_kernels):
    """The blocked slots looked up and accumulated by the two kernels
    (rows of one width: all five slots; the ragged table's too, its
    padding cells value 0), one device and four joined by the real
    ``psum``: within the tolerance XLA's walk is held to, and counted."""
    rng = np.random.default_rng(11)
    rows_of = CASES[case](rng)
    logistic = cls is FMClassifier
    y = _labels(rng, 1500, logistic)
    batch = 128 * shards * 2
    mesh = DeviceMesh(devices=jax.devices()[:shards])
    with _delta("fm") as counts:
        model = _estimator(cls, mesh=mesh, batch=batch).fit(_table(rows_of, y))
    assert counts["fused_block_fits"] == 1 and counts["fits"] == 1
    assert counts["blocked_cells"] == counts["cells"]
    want = _want(rows_of, y, logistic=logistic, shards=shards, batch=batch)
    assert _gap(model, want) <= TOL


@pytest.mark.parametrize("case", ["batch of 100", "too long", "one pass"])
def test_a_fit_the_kernels_do_not_take_is_the_fit_as_it_was(case, monkeypatch):
    """On a TPU (the gate told so; a kernel traced here would fail to
    lower): a device's batch that is not whole tiles, a block whose parts
    fast memory would not hold, the control's one bfloat16 pass each keep
    XLA's walk, give the model a CPU's fit gives to the bit, and count
    ``fused_block_fits`` 0."""
    from flinkml_tpu.kernels import _mosaic, payload_blocks

    rng = np.random.default_rng(11)
    rows_of = CASES["one width"](rng)
    y = _labels(rng, 1500)
    batch = 100 if case == "batch of 100" else BATCH
    precision = (jax.lax.Precision.DEFAULT if case == "one pass"
                 else _fm_sparse.LOOKUP_PRECISION)

    def fit():
        _fm_sparse._trainer.cache_clear()
        est = _estimator(batch=batch)
        with _delta("fm") as counts:
            w0, w, v = _fm_sparse.fit_csr(est, _table(rows_of, y), True, precision)
        return (w0, w, v), counts

    plain, _ = fit()
    monkeypatch.setattr(_mosaic, "interpret_mode", lambda: False)     # a TPU
    if case == "too long":
        monkeypatch.setattr(payload_blocks, "_RESIDENT_BYTES", 1 << 16)
    else:
        assert _fm_sparse._walk_in_fast_memory(
            jnp.float32, BATCH, (384,) * 5, K + 1, _fm_sparse.LOOKUP_PRECISION)
    try:
        as_it_was, counts = fit()
    finally:
        _fm_sparse._trainer.cache_clear()
    assert counts.get("fused_block_fits", 0.0) == 0 and counts["fits"] == 1
    for a, b in zip(plain, as_it_was):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_where_the_walk_runs_in_fast_memory_is_read_off_the_fit(monkeypatch):
    from flinkml_tpu.kernels import _mosaic

    plan = (128, 256, None, 26624)
    high, low = _fm_sparse.LOOKUP_PRECISION, jax.lax.Precision.DEFAULT
    taken = _fm_sparse._walk_in_fast_memory
    # here, on a CPU, Mosaic's kernels would be interpreted: XLA's products
    assert not taken(jnp.float32, 65_536, plan, 17, high)
    monkeypatch.setattr(_mosaic, "interpret_mode", lambda: False)     # a TPU
    assert taken(jnp.float32, 65_536, plan, 17, high)
    assert taken(jnp.float32, 128, plan, 7, high)
    assert not taken(jnp.float64, 65_536, plan, 17, high)
    assert not taken(jnp.float32, 100, plan, 17, high)
    assert not taken(jnp.float32, 65_536, plan, 17, low)    # the control
    assert not taken(jnp.float32, 65_536, (), 17, high)
    assert not taken(jnp.float32, 65_536, (None,) * 4, 17, high)
    assert not taken(jnp.float32, 65_536, (194_560, 128), 17, high)
    # the walk: the shortest blocks first, each length's slots in turn
    assert _fm_sparse._walk(plan) == ([128, 256, 26624], [0, 1, 3])
    assert _fm_sparse._walk((256, 128, None, 256)) == ([128, 256, 256], [1, 0, 3])


def test_overlapping_blocks_add_through_the_kernels_too(through_the_kernels):
    """:func:`test_overlapping_blocks_add_and_agree_with_no_plan`'s step,
    the blocked slots in the kernels: two short blocks on one row of 128
    columns, a third over both, a long one over all three, a general
    slot beside them."""
    rng = np.random.default_rng(13)
    rows, dim = 512, 5000
    idx = np.stack([rng.integers(0, 100, rows), rng.integers(20, 128, rows),
                    rng.integers(90, 250, rows), rng.integers(0, 2500, rows),
                    rng.integers(0, dim, rows)], axis=1).astype(np.int32)
    val = rng.standard_normal((rows, 5)).astype(np.float32)
    y, wt = _labels(rng, rows), np.ones(rows, np.float32)
    table = (0.1 * rng.standard_normal((K + 1, 5120))).astype(np.float32)
    table[:, dim:] = 0
    params = (jnp.asarray([0.1], jnp.float32), jnp.asarray(table.reshape(K + 1, 40, 128)))
    zeros = jax.tree.map(jnp.zeros_like, params)
    mesh, spec = _one_device(), jax.sharding.PartitionSpec()

    def run(plan, starts):
        step = _fm_sparse.make_step(True, rows, "data", plan)
        with jax.enable_x64(False):
            return jax.jit(jax.shard_map(
                lambda *a: step(params, zeros, zeros, jnp.int32(0), *a,
                                jnp.float32(0.01), jnp.float32(1e-3)),
                mesh=mesh.mesh, in_specs=(spec,) * 5, out_specs=spec))(
                    idx, val, y, wt, starts)

    planned = run((128, 128, 256, 2560, None), np.zeros(5, np.int32))
    general = run((), np.zeros(1, np.int32))
    for a, b in zip(jax.tree.leaves(planned[1]), jax.tree.leaves(general[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=2e-8)
    assert float(planned[3]) == pytest.approx(float(general[3]), rel=1e-6)


# -- the hand-over (PR 56) ----------------------------------------------------

def test_the_model_holds_the_buffers_the_read_back_returned(monkeypatch):
    """No pass over the parameters on the host: ``w`` and ``V`` are the
    arrays ONE ``jax.device_get`` returned (views: the padding cut, a
    column's factors a row), float32 and C-contiguous, ``get_model_data`` hands out views
    of them, and the fit counts itself."""
    rng = np.random.default_rng(17)
    rows_of = _field_rows(rng, 600)
    dim = rows_of[3]
    read = []
    device_get = jax.device_get

    def spy(tree):
        read.append(device_get(tree))
        return read[-1]

    monkeypatch.setattr(jax, "device_get", spy)
    with _delta("fm") as counts:
        model = _estimator().fit(_table(rows_of, _labels(rng, 600)))
    monkeypatch.undo()
    assert counts["handover_view_fits"] == 1 and counts["fits"] == 1
    (w0, w, v, steps), = read                        # one read-back a fit
    assert w0.shape == (1,) and int(steps) == STEPS
    # in rows of 128 lanes, the padding past dim (1,500 of 1,536) last
    assert w.shape == (12, 128) and v.shape == (12 * K, 128)
    assert model._w0 == float(w0[0]) and isinstance(model._w0, float)
    assert np.shares_memory(model._w, w) and np.shares_memory(model._v, v)
    for held, shape in ((model._w, (dim,)), (model._v, (dim, K))):
        assert held.dtype == np.float32 and held.shape == shape
        assert held.flags.c_contiguous
    data = model.get_model_data()[0]
    assert np.shares_memory(data.column("w"), model._w)
    assert np.shares_memory(data.column("v"), model._v)
    assert data.column("w").dtype == data.column("v").dtype == np.float32
    assert data.column("w").shape == (1, dim) and data.column("v").shape == (1, dim, K)


def test_a_model_that_copies_its_parameters_is_not_counted(monkeypatch):
    """The counter reads the arrays, not a flag: a model that widened
    what it was handed (the parent's) counts 0."""
    rng = np.random.default_rng(17)
    table = _table(_field_rows(rng, 600), _labels(rng, 600))
    monkeypatch.setattr(fm, "_floating", lambda a: np.asarray(a, np.float64))
    with _delta("fm") as counts:
        model = _estimator().fit(table)
    assert model._v.dtype == np.float64
    assert counts["fits"] == 1 and "handover_view_fits" not in counts


def test_the_devices_turn_is_the_hosts_turn_of_the_raw_table(monkeypatch):
    """``fit_csr``'s ``(w0, w, V)`` against the parent's layout of the
    table the loop returned (``table[0, :dim]``, ``table[1:, :dim].T``),
    to the bit, at a ``dim`` (1,500) that is no multiple of 128."""
    rng = np.random.default_rng(18)
    rows_of = _field_rows(rng, 600)
    dim = rows_of[3]
    assert dim % 128 and _fm_sparse.padded_dim(dim) == 1536
    raw = []
    handover = _fm_sparse._handover

    def spy(table):
        raw.append(np.asarray(table))
        return handover(table)

    monkeypatch.setattr(_fm_sparse, "_handover", spy)
    w0, w, v = _fm_sparse.fit_csr(_estimator(), _table(rows_of, _labels(rng, 600)), True)
    (table,) = raw
    assert table.shape == (K + 1, 12, 128) and table.dtype == np.float32
    planes = table.reshape(K + 1, -1)
    assert not planes[:, dim:].any() and planes[1:, :dim].any()
    assert w.tobytes() == planes[0, :dim].tobytes()
    assert v.tobytes() == np.ascontiguousarray(planes[1:, :dim].T).tobytes()
    assert (w0.shape, w.shape, v.shape) == ((1,), (dim,), (dim, K))
    assert w0.dtype == w.dtype == v.dtype == np.float32


def _model_of(cls, w0, w, v):
    model = cls().set_features_col("features")
    return model.set_model_data(Table({"w0": np.asarray([w0]), "w": w[None], "v": v[None]}))


@pytest.mark.parametrize("cls", [fm.FMClassifierModel, fm.FMRegressorModel])
@pytest.mark.parametrize("path", ["CsrColumn", "SparseVector column", "dense matrix"])
def test_a_float32_model_scores_as_its_float64_copy_to_the_bit(path, cls):
    """Every margin is float64 arithmetic, the parameters widened as they
    are gathered: the model a fit makes (float32) against a clone handed
    ``astype(np.float64)`` copies (what the parent's model held)."""
    rng = np.random.default_rng(19)
    rows_of = _hashed_rows(rng, 300, dim=400)
    dim, idx, val = rows_of[3:]
    w = rng.standard_normal(dim).astype(np.float32)
    v = (0.3 * rng.standard_normal((dim, K))).astype(np.float32)
    narrow = _model_of(cls, 0.25, w, v)
    wide = _model_of(cls, 0.25, w.astype(np.float64), v.astype(np.float64))
    assert narrow._v.dtype == np.float32 and wide._v.dtype == np.float64
    table = _table(rows_of, np.zeros(300, np.float32))
    if path == "SparseVector column":
        table = Table({"features": table.column("features")})
        assert table.column("features").dtype == object
    elif path == "dense matrix":
        table = Table({"features": reference.densified(idx, val, dim)})
    for column in ("prediction",) + (("rawPrediction",) if "Classifier" in cls.__name__ else ()):
        got = np.asarray(narrow.transform(table)[0].column(column))
        want = np.asarray(wide.transform(table)[0].column(column))
        assert got.dtype == np.float64 and got.tobytes() == want.tobytes()


def test_save_load_and_set_model_data_keep_the_floating_dtype(tmp_path):
    rng = np.random.default_rng(20)
    rows_of = _field_rows(rng, 300)
    table = _table(rows_of, _labels(rng, 300))
    model = _estimator(steps=3).fit(table)
    model.save(str(tmp_path / "narrow"))
    loaded = fm.FMClassifierModel.load(str(tmp_path / "narrow"))
    assert loaded._w.dtype == loaded._v.dtype == np.float32
    assert loaded._v.tobytes() == model._v.tobytes() and loaded._w0 == model._w0
    want = np.asarray(model.transform(table)[0].column("rawPrediction"))
    assert np.asarray(loaded.transform(table)[0].column("rawPrediction")
                      ).tobytes() == want.tobytes()
    # what an earlier tree saved (float64) loads and scores as float64
    wide = _model_of(fm.FMClassifierModel, model._w0, model._w.astype(np.float64),
                     model._v.astype(np.float64))
    assert wide._w.dtype == wide._v.dtype == np.float64
    wide.save(str(tmp_path / "wide"))
    loaded = fm.FMClassifierModel.load(str(tmp_path / "wide"))
    assert loaded._w.dtype == loaded._v.dtype == np.float64
    assert np.asarray(loaded.transform(table)[0].column("rawPrediction")
                      ).tobytes() == want.tobytes()
    # a table of whole numbers is held as float64
    whole = _model_of(fm.FMClassifierModel, 1, np.arange(rows_of[3]),
                      np.ones((rows_of[3], K), np.int32))
    assert whole._w.dtype == whole._v.dtype == np.float64 and whole._w0 == 1.0


# -- the API's edges ----------------------------------------------------------

def test_the_model_scores_a_csr_column_from_its_arrays():
    rng = np.random.default_rng(14)
    rows_of = _hashed_rows(rng, 400)
    y = _labels(rng, 400)
    table = _table(rows_of, y)
    model = _estimator().fit(table)
    with _delta("table") as tab:
        out = model.transform(table)[0]
    assert tab.get("csr_rows_materialized", 0.0) == 0.0
    dim, idx, val = rows_of[3:]
    want = reference.margin(model._w0, model._w, model._v, idx,
                            val.astype(np.float64))[0]
    prob = np.asarray(out.column("rawPrediction"))[:, 1]
    np.testing.assert_allclose(prob, 1 / (1 + np.exp(-want)), rtol=1e-12)
    # the same rows as objects take the old path to the same margins
    objects = Table({"features": table.column("features"), "label": y})
    np.testing.assert_allclose(
        np.asarray(model.transform(objects)[0].column("rawPrediction"))[:, 1],
        prob, rtol=1e-9)


def test_what_the_sparse_fit_refuses():
    rng = np.random.default_rng(15)
    rows_of = _field_rows(rng, 100)
    with pytest.raises(ValueError, match="labels in"):
        _estimator().fit(_table(rows_of, np.arange(100, dtype=np.float32)))
    from flinkml_tpu.sharding import EMBEDDING

    est = _estimator()
    est.sharding_plan = EMBEDDING
    with pytest.raises(ValueError, match="CsrColumn"):
        est.fit(_table(rows_of, _labels(rng, 100)))
    # the regressor takes any labels
    _estimator(FMRegressor, steps=2).fit(
        _table(rows_of, np.arange(100, dtype=np.float32)))


def test_a_dense_column_keeps_the_dense_fit():
    """``_adam``'s draw: nothing of the sparse fit runs."""
    rng = np.random.default_rng(16)
    x = rng.standard_normal((200, 12))
    y = (rng.random(200) < 0.5).astype(np.float64)
    with _delta("fm") as counts:
        FMClassifier().set_max_iter(3).set_factor_size(4).fit(
            Table({"features": x, "label": y}))
    assert counts == {}
