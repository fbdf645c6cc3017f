"""The blocked sparse step (PR 29): ELL slots whose columns sit in a block
of their own are looked up and accumulated by two-level one-hot products
(``ops.sparse.block_lookup``, ``block_accumulate``) under the plan
``ops.sparse.slot_block_plan`` reads off the cells.

- the lookup is the gather bit for bit, and the same lookup in a table
  rounded to bfloat16 is not: the test can tell float32 from the next
  precision down;
- the accumulate is ``zeros.at[idx].add(c)`` to float32 rounding, and
  repeats to the bit;
- the step under a mixed plan (blocked and general slots, two blocks
  overlapping) agrees with the step under no plan, three losses, on the
  eight-device mesh;
- the planner: one plan for two seeds of a field-blocked table and for
  two tables of one schema whose columns start elsewhere (the starts
  are an operand), none for rows hashed over all of ``dim``;
- a field-blocked table with cells missing (ragged rows) is laid one
  field a slot and planned the same way; hashed or text-like ragged
  rows keep their padded buckets;
- the sparse trainer under the empty plan lowers to the text of the
  step as it was before plans existed (a copy of it, kept here).
"""

from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from flinkml_tpu.models import _linear_sgd
from flinkml_tpu.ops import sparse
from flinkml_tpu.utils.metrics import metrics

#: Block lengths either side of 128 rows of 128 lanes: below it the lane
#: index is contracted on the MXU, from it on the row index.
LENGTHS = [128, 256, 1024, 3072, 128 * 128, 128 * 136]


def _cells(length, slots=3, rows=700, seed=0):
    rng = np.random.default_rng(seed)
    blocks = rng.standard_normal((slots, length)).astype(np.float32)
    blocks[0, :4] = [0.0, 1e-30, -3.5e20, np.float32(1) + np.float32(2) ** -23]
    local = rng.integers(0, length, (slots, rows)).astype(np.int32)
    local[0, :4] = [0, 1, 2, 3]
    return blocks, local, rng.standard_normal((slots, rows)).astype(np.float32)


@pytest.mark.parametrize("length", LENGTHS)
def test_block_lookup_is_the_gather_bit_for_bit(length):
    blocks, local, _ = _cells(length)
    got = np.asarray(jax.jit(sparse.block_lookup)(blocks, local))
    want = np.take_along_axis(blocks, local, axis=1)
    assert got.dtype == np.float32
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("length", [256, 128 * 136])
def test_a_bfloat16_lookup_fails_the_bit_for_bit_test(length):
    """The control: the same lookup in a table rounded to bfloat16, which
    is what any precision under ``HIGHEST`` makes of it on the MXU."""
    blocks, local, _ = _cells(length)
    low = jnp.asarray(blocks).astype(jnp.bfloat16).astype(jnp.float32)
    got = np.asarray(jax.jit(sparse.block_lookup)(low, local))
    want = np.take_along_axis(blocks, local, axis=1)
    assert np.mean(got != want) > 0.9
    np.testing.assert_allclose(got, want, rtol=2.0 ** -8)


def test_the_products_ask_for_the_highest_precision():
    """On the CPU every precision is float32, so the bits cannot tell:
    the request itself is checked."""
    blocks, local, contrib = _cells(1024)
    for text in (
            jax.jit(sparse.block_lookup).lower(blocks, local).as_text(),
            jax.jit(sparse.block_accumulate, static_argnums=2).lower(
                local, contrib, 1024).as_text()):
        assert text.count("dot_general") == 1
        assert "precision = [HIGHEST, HIGHEST]" in text


@pytest.mark.parametrize("length", LENGTHS)
def test_block_accumulate_is_the_scatter_add_and_repeats(length):
    _, local, contrib = _cells(length, rows=5000)
    accumulate = jax.jit(sparse.block_accumulate, static_argnums=2)
    got = np.asarray(accumulate(local, contrib, length))
    assert got.shape == (local.shape[0], length) and got.dtype == np.float32
    exact = np.zeros(got.shape)
    for s in range(local.shape[0]):
        np.add.at(exact[s], local[s], contrib[s].astype(np.float64))
    scatter = np.stack([
        np.asarray(jnp.zeros(length, jnp.float32).at[local[s]].add(contrib[s]))
        for s in range(local.shape[0])])
    # Float32 rounding: as far from float64 as the scatter-add is.
    assert np.abs(got - exact).max() < 4 * max(
        np.abs(scatter - exact).max(), 2.0 ** -22)
    assert got.tobytes() == np.asarray(
        accumulate(local, contrib, length)).tobytes()


def test_cells_outside_the_block_with_value_zero_change_nothing():
    """The zero rows a shard is padded with: index 0 in every slot."""
    blocks, local, contrib = _cells(1024)
    local[:, 10:20] = -7000
    contrib[:, 10:20] = 0.0
    looked = np.asarray(sparse.block_lookup(blocks, local))
    assert np.isfinite(looked).all()
    kept = np.ones(local.shape[1], bool)
    kept[10:20] = False
    np.testing.assert_array_equal(
        np.asarray(sparse.block_accumulate(local, contrib, 1024)),
        np.asarray(sparse.block_accumulate(
            local[:, kept], contrib[:, kept], 1024)))


# -- the step -----------------------------------------------------------------

DIM, WIDTH, BS = 5000, 6, 16
#: Slots 0 and 1 share a block of 128 at 0 and slot 2's block of 256
#: overlaps it; slot 4 sits in one that passes ``dim`` (the last rows of
#: 128, zeros past the end); 3 and 5 gather. Starts in rows of 128.
MIXED_PLAN = (128, 128, 256, None, 1024, None)
MIXED_STARTS = np.asarray([0, 0, 0, 0, 32, 0], np.int32)
TOP = 32 * 128


def _step_rows(rows, seed=1):
    rng = np.random.default_rng(seed)
    idx = np.stack([
        rng.integers(0, 100, rows), rng.integers(20, 128, rows),
        rng.integers(90, 250, rows), rng.integers(0, DIM, rows),
        rng.integers(TOP, DIM, rows), rng.integers(0, DIM, rows),
    ], axis=1).astype(np.int32)
    val = rng.standard_normal((rows, WIDTH)).astype(np.float32)
    y = (rng.random(rows) < 0.4).astype(np.float32)
    w = (rng.random(rows) + 0.5).astype(np.float32)
    return idx, val, y, w


def _run_step(mesh, loss, plan, data, coef, epoch, starts=None):
    step = _linear_sgd.make_sparse_step_bucketed(
        loss, (BS,), "data", DIM, "xla", plan)
    if plan:
        data += (mesh.shard_batch(np.tile(starts, mesh.axis_size())),)
    f = jax.jit(jax.shard_map(
        lambda c, e, *placed: step(
            c, e, *placed, jnp.float32(0.3), jnp.float32(0.01),
            jnp.float32(0.001)),
        mesh=mesh.mesh, in_specs=(P(), P()) + (P("data"),) * len(data),
        out_specs=(P(), P())))
    new_coef, loss_value = f(coef, jnp.asarray(epoch, jnp.int32), *data)
    return np.asarray(new_coef), float(loss_value)


@pytest.mark.parametrize("loss", ["logistic", "hinge", "squared"])
def test_the_blocked_step_agrees_with_the_general_step(mesh, loss):
    p = mesh.axis_size()
    data = tuple(jax.device_put(a, NamedSharding(mesh.mesh, P("data")))
                 for a in _step_rows(p * 3 * BS))
    coef = jnp.asarray(
        np.random.default_rng(2).standard_normal(DIM).astype(np.float32))
    for epoch in (0, 2):
        want, want_loss = _run_step(mesh, loss, (), data, coef, epoch)
        got, got_loss = _run_step(
            mesh, loss, MIXED_PLAN, data, coef, epoch, MIXED_STARTS)
        assert np.abs(want - np.asarray(coef)).max() > 1e-3
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
        assert got_loss == pytest.approx(want_loss, rel=1e-6)


def test_blocks_that_start_elsewhere_run_the_same_step(mesh):
    """The starts are an operand: the cells moved by three rows of 128,
    and the coefficients with them, give the same step three rows on."""
    p = mesh.axis_size()
    idx, val, y, w = _step_rows(p * 3 * BS)
    low, top = TOP - 384, DIM - 384                # where slot 4's block goes
    for slot in (3, 5):                            # the gathered cells stay
        idx[:, slot] = 640 + idx[:, slot] % (low - 640)
    coef = np.random.default_rng(3).standard_normal(DIM).astype(np.float32)
    moved = coef.copy()
    moved[384:640], moved[:384] = coef[:256], coef[256:640]
    moved[low:top], moved[top:] = coef[TOP:], coef[low:TOP]
    shift = np.asarray([3, 3, 3, 0, -3, 0], np.int32)
    place = lambda *arrays: tuple(
        jax.device_put(a, NamedSharding(mesh.mesh, P("data"))) for a in arrays)
    want, want_loss = _run_step(
        mesh, "logistic", MIXED_PLAN, place(idx, val, y, w),
        jnp.asarray(coef), 1, MIXED_STARTS)
    got, got_loss = _run_step(
        mesh, "logistic", MIXED_PLAN, place(idx + 128 * shift, val, y, w),
        jnp.asarray(moved), 1, MIXED_STARTS + shift)
    assert got_loss == want_loss
    np.testing.assert_array_equal(got[384:640], want[:256])
    np.testing.assert_array_equal(got[low:top], want[TOP:])
    np.testing.assert_array_equal(got[640:low], want[640:low])


def test_a_plan_with_several_buckets_is_refused():
    with pytest.raises(ValueError, match="one-bucket"):
        _linear_sgd.make_sparse_step_bucketed(
            "logistic", (8, 8), "data", DIM, "xla", MIXED_PLAN)


# -- the planner --------------------------------------------------------------

CARDS, STRATUM, PLAN_DIM = (64, 3, 700, 5000, 40000), 2100, 12000
FIELD_PLAN = (128, 128, 1024, 3072, 3072)
#: In rows of 128: the fields start at 0, 2100, 4200, 6300, 8400.
FIELD_STARTS = [0, 16, 32, 49, 65]


def _field_rows(seed, rows=20000, offset=0):
    """One cell a field, ascending: field ``f`` owns columns ``[f *
    STRATUM, (f + 1) * STRATUM)`` (moved up by ``offset``), skewed to
    its head, large fields folded into the stratum
    (``benchmark/datagen_criteo``'s sampler)."""
    rng = np.random.default_rng(seed)
    card = np.asarray(CARDS)
    rank = np.floor(rng.random((rows, card.size)) ** 3 * card).astype(np.int64)
    return (rank % STRATUM + np.arange(card.size) * STRATUM
            + offset).astype(np.int32)


def _plan(indices, dim, step_rows=64):
    with ThreadPoolExecutor(4) as pool:
        plan, starts = sparse.slot_block_plan(indices, dim, step_rows, pool)
    return plan, None if starts is None else starts.tolist()


def test_two_seeds_of_a_field_blocked_table_give_one_plan():
    first, second = _plan(_field_rows(1), PLAN_DIM), _plan(_field_rows(2), PLAN_DIM)
    assert first == second == (FIELD_PLAN, FIELD_STARTS)
    for slot, (start, length) in enumerate(zip(FIELD_STARTS, FIELD_PLAN)):
        cells = np.concatenate([_field_rows(1)[:, slot], _field_rows(2)[:, slot]])
        assert 128 * start <= cells.min() and cells.max() < 128 * start + length


def test_tables_of_one_schema_share_a_plan_wherever_their_columns_start():
    """Another lowest category, a split that lost the head of a field:
    the plan (what keys the program) stays, the starts move."""
    plan, starts = _plan(_field_rows(1, offset=384), PLAN_DIM + 384)
    assert plan == FIELD_PLAN
    assert starts == [3, 19, 35, 52, 68]
    rows = _field_rows(1)
    rows[:, 0] = np.maximum(rows[:, 0], 5)        # the lowest ids never seen
    rows[:, 3] = np.maximum(rows[:, 3], 6310)
    assert _plan(rows, PLAN_DIM) == (FIELD_PLAN, FIELD_STARTS)


def test_a_block_that_would_pass_dim_is_moved_down_and_wide_slots_stay_general(
        monkeypatch):
    rows = _field_rows(3)
    plan, starts = _plan(rows, 5 * STRATUM)
    # 10,500 columns are 83 rows of 128, the block 24 of them.
    assert plan == FIELD_PLAN and starts == FIELD_STARTS[:4] + [83 - 24]
    monkeypatch.setattr(sparse, "BLOCK_MAX_COLUMNS", 1024)
    assert _plan(rows, PLAN_DIM) == ((128, 128, 1024, None, None),
                                     [0, 16, 32, 0, 0])


def test_a_one_hot_is_bounded_by_splitting_groups_and_leaving_long_blocks(
        monkeypatch):
    monkeypatch.setattr(sparse, "_BLOCK_ONE_HOT_ELEMENTS", 64 * 24)
    # 24 rows of 128 at most over 64 rows a step: 3072 columns.
    plan, _ = _plan(_field_rows(3), PLAN_DIM)
    assert plan == FIELD_PLAN
    assert _plan(_field_rows(3), PLAN_DIM, 65)[0][3:] == (None, None)
    assert sparse.block_groups(plan, 64) == [
        (128, [0, 1]), (1024, [2]), (3072, [3]), (3072, [4])]
    monkeypatch.setattr(sparse, "_BLOCK_ONE_HOT_ELEMENTS", 64 * 48)
    assert [slots for _, slots in sparse.block_groups(plan, 64)] == [
        [0, 1], [2], [3, 4]]
    assert sparse.block_groups((), 64) == []


def test_the_plan_of_a_table_in_tasks_is_the_plan_of_the_whole(monkeypatch):
    rows = _field_rows(4, rows=1000)
    whole = _plan(rows, PLAN_DIM)
    monkeypatch.setattr(sparse, "_PLAN_TASK_ROWS", 96)
    monkeypatch.setattr(sparse, "_PLAN_FOLD", 7)
    assert _plan(rows, PLAN_DIM) == whole
    lows, highs = sparse._column_ranges(rows[:5])
    np.testing.assert_array_equal(lows, rows[:5].min(axis=0))
    np.testing.assert_array_equal(highs, rows[:5].max(axis=0))


def _prepare(mesh, indptr, indices, dim, values=None):
    n = indptr.size - 1
    names = ("cells", "blocked_cells", "blocked_slots", "buckets")

    def read():
        counters = metrics.group("hostdata.sparse").snapshot()["counters"]
        return {k: counters.get(k, 0.0) for k in names}

    before = read()
    if values is None:
        values = np.ones(indices.size, np.float32)
    place, sizes, plan = _linear_sgd.prepare_sparse_buckets(
        indptr, indices, values, dim, np.zeros(n, np.float32), None, mesh, 64,
        seed=0)
    data = _linear_sgd._placed(place(0, 1))
    assert len(data) == 4 * len(sizes) + bool(plan)
    return plan, data, {k: v - before[k] for k, v in read().items()}


def test_rows_hashed_over_all_of_dim_have_the_empty_plan(mesh):
    """Slot ``j`` is the ``j``-th smallest of the row's hashes: over many
    rows it spans most of ``dim``."""
    rng = np.random.default_rng(5)
    dim, rows, width = 1_000_000, 4000, 8
    indices = np.sort(rng.integers(0, dim, (rows, width)), axis=1).astype(np.int32)
    indptr = np.arange(rows + 1, dtype=np.int64) * width
    plan, _, counted = _prepare(mesh, indptr, indices.reshape(-1), dim)
    assert plan == ()
    assert counted == {"cells": rows * width, "blocked_cells": 0.0,
                       "blocked_slots": 0.0, "buckets": 1.0}


def test_a_field_blocked_table_is_planned_and_counted(mesh):
    indices = _field_rows(6, rows=3000)
    rows, width = indices.shape
    indptr = np.arange(rows + 1, dtype=np.int64) * width
    plan, data, counted = _prepare(mesh, indptr, indices.reshape(-1), PLAN_DIM)
    assert plan == FIELD_PLAN
    starts = np.asarray(data[-1]).reshape(mesh.axis_size(), width)
    assert (starts == FIELD_STARTS).all()
    assert counted == {"cells": rows * width, "blocked_cells": rows * width,
                       "blocked_slots": width, "buckets": 1.0}


def test_another_training_dtype_has_no_plan(mesh):
    indices = _field_rows(8, rows=512)
    rows, width = indices.shape
    place, sizes, plan = _linear_sgd.prepare_sparse_buckets(
        np.arange(rows + 1, dtype=np.int64) * width, indices.reshape(-1),
        np.ones(indices.size), PLAN_DIM, np.zeros(rows), None, mesh, 64,
        dtype=jnp.bfloat16, seed=0)
    assert plan == () and len(_linear_sgd._placed(place(0, 1))) == 4


# -- ragged rows ----------------------------------------------------------------


def _ragged(indices, keep, values=None):
    indptr = np.zeros(indices.shape[0] + 1, np.int64)
    np.cumsum(keep.sum(axis=1), out=indptr[1:])
    return indptr, indices[keep], None if values is None else values[keep]


def _drop(shape, seed, share=0.05):
    """A mask with a cell in twenty missing, every slot hit, rows 0-2
    whole, row 3 empty."""
    keep = np.random.default_rng(seed).random(shape) >= share
    keep[:3] = True
    keep[3] = False
    return keep


def test_a_field_blocked_table_with_cells_missing_is_laid_one_field_a_slot():
    indices = _field_rows(7, rows=3000)
    values = np.random.default_rng(7).standard_normal(
        indices.shape).astype(np.float32)
    keep = _drop(indices.shape, 7)
    indptr, idx, val = _ragged(indices, keep, values)
    with ThreadPoolExecutor(3) as pool:
        block = sparse.align_ragged_rows(indptr, idx, val, np.float32, pool)
    starts = indices.min(axis=0)
    np.testing.assert_array_equal(
        block["indices"], np.where(keep, indices, starts))
    np.testing.assert_array_equal(block["values"], np.where(keep, values, 0))
    np.testing.assert_array_equal(block["slot_cells"], keep.sum(axis=0))
    assert block["indices"].dtype == np.int32
    assert block["values"].dtype == np.float32


def test_the_aligned_table_in_tasks_is_the_aligned_table(monkeypatch):
    indices = _field_rows(9, rows=1000)
    keep = _drop(indices.shape, 9)
    indptr, idx, _ = _ragged(indices, keep)
    val = np.ones(idx.size, np.float32)
    with ThreadPoolExecutor(3) as pool:
        whole = sparse.align_ragged_rows(indptr, idx, val, np.float32, pool)
        monkeypatch.setattr(sparse, "_ALIGN_TASK_ROWS", 96)
        tasks = sparse.align_ragged_rows(indptr, idx, val, np.float32, pool)
    for name in whole:
        np.testing.assert_array_equal(tasks[name], whole[name])


@pytest.mark.parametrize("case", ["hashed", "text", "unsorted", "two-in-a-field",
                                  "under-the-first-field"])
def test_rows_that_keep_to_no_fields_are_not_aligned(case):
    rng = np.random.default_rng(11)
    indices = _field_rows(11, rows=2000)
    keep = _drop(indices.shape, 11)
    if case == "hashed":
        indices = np.sort(rng.integers(0, PLAN_DIM, indices.shape), axis=1
                          ).astype(np.int32)
    elif case == "text":                      # widths 0 to 5: mostly padding
        keep = np.arange(indices.shape[1]) < rng.integers(
            0, 6, indices.shape[0])[:, None]
    elif case == "unsorted":
        indices[50] = indices[50, ::-1]
    elif case == "two-in-a-field":            # a row with field 1's cell twice
        indices[60, 2], keep[60] = indices[60, 1] + 1, True
        keep[60, 4] = False
    else:                                     # a cell under every widest row's
        indices[:, 0] += 7
        indices[70, 0], keep[70] = 2, True
        keep[70, 3] = False
    indptr, idx, _ = _ragged(indices, keep)
    with ThreadPoolExecutor(2) as pool:
        assert sparse.align_ragged_rows(
            indptr, idx, np.ones(idx.size, np.float32), np.float32, pool) is None


def test_a_field_blocked_table_with_cells_missing_is_planned(mesh):
    """A one-hot encoder that drops a category, a file that leaves zeros
    out: one missing cell does not cost the table its plan."""
    indices = _field_rows(7, rows=3000)
    keep = _drop(indices.shape, 7)
    indptr, idx, _ = _ragged(indices, keep)
    plan, data, counted = _prepare(mesh, indptr, idx, PLAN_DIM)
    assert plan == FIELD_PLAN
    assert counted == {"cells": keep.sum(), "blocked_cells": keep.sum(),
                       "blocked_slots": 5.0, "buckets": 1.0}
    assert data[0].shape[1] == 5


def test_ragged_rows_with_no_blocked_slot_keep_their_buckets(mesh, monkeypatch):
    indices = _field_rows(7, rows=3000)
    keep = _drop(indices.shape, 7)
    indptr, idx, _ = _ragged(indices, keep)
    monkeypatch.setattr(sparse, "BLOCK_MAX_COLUMNS", 0)
    plan, data, counted = _prepare(mesh, indptr, idx, PLAN_DIM)
    assert plan == () and counted["buckets"] > 1 and len(data) % 4 == 0


@pytest.mark.parametrize("loss", ["logistic", "squared"])
def test_the_fit_of_an_aligned_table_is_the_fit_of_its_buckets(
        mesh, loss, monkeypatch):
    """Ragged field rows through the plan against the same rows through
    the padded buckets and the general step: other batches a step (the
    buckets are stratified by width), so full-batch steps are compared."""
    indices = _field_rows(12, rows=512)
    values = np.random.default_rng(12).standard_normal(
        indices.shape).astype(np.float32)
    keep = _drop(indices.shape, 12, share=0.1)
    indptr, idx, val = _ragged(indices, keep, values)
    y = (np.random.default_rng(13).random(512) < 0.4).astype(np.float32)

    def fit():
        return _linear_sgd.train_linear_model_sparse_csr(
            indptr, idx, val, PLAN_DIM, y, None, loss=loss, mesh=mesh,
            max_iter=5, learning_rate=0.5, global_batch_size=512, reg=0.0,
            elastic_net=0.0, tol=0.0, seed=3)

    planned = fit()
    monkeypatch.setattr(sparse, "BLOCK_MAX_COLUMNS", 0)
    np.testing.assert_allclose(planned, fit(), rtol=0, atol=2e-6)
    assert np.abs(planned).max() > 1e-2


def test_the_one_hot_encoders_default_output_field_by_field_is_planned(mesh):
    """``OneHotEncoder`` as it comes (``dropLast``: a field's last
    category is the empty vector, so rows are ragged), its sparse
    outputs set side by side as one vector: every cell is blocked."""
    from flinkml_tpu.linalg import SparseVector
    from flinkml_tpu.models import LogisticRegression, OneHotEncoder
    from flinkml_tpu.table import CsrColumn, Table

    rng = np.random.default_rng(21)
    cards, rows = (5, 40, 300, 3, 700), 2000
    raw = {f"c{f}": np.floor(rng.random(rows) ** 2 * card)
           for f, card in enumerate(cards)}
    for f, card in enumerate(cards):
        raw[f"c{f}"][f] = card - 1                    # the last category is seen
    encoder = (OneHotEncoder().set_input_cols(list(raw))
               .set_output_cols([f"o{f}" for f in range(len(cards))])
               .set_output_format("sparse"))
    (encoded,) = encoder.fit(Table(raw)).transform(Table(raw))
    parts = [encoded[f"o{f}"] for f in range(len(cards))]
    sizes = [part[0].size() for part in parts]
    assert sizes == [card - 1 for card in cards]
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    vectors = [SparseVector(
        int(offsets[-1]),
        np.concatenate([part[r].indices + off for part, off in zip(parts, offsets)]),
        np.concatenate([part[r].values for part in parts]))
        for r in range(rows)]
    column = CsrColumn.from_vectors(vectors)
    widths = np.diff(column.indptr)
    assert widths.min() < widths.max() == len(cards)
    table = Table({"features": column,
                   "label": (raw["c1"] < 3).astype(np.float64)})
    counters = metrics.group("hostdata.sparse")
    before = dict(counters.snapshot()["counters"])
    LogisticRegression().set_seed(0).set_max_iter(3).set_global_batch_size(
        256).fit(table)
    added = {k: v - before.get(k, 0.0)
             for k, v in counters.snapshot()["counters"].items()}
    assert added["blocked_slots"] == len(cards)
    assert added["blocked_cells"] == added["cells"] == column.indices.size
    assert added["buckets"] == 1.0


# -- the program no plan reaches ------------------------------------------------


def _step_before_plans(loss, local_bss, axis, dim, segsum_backend="xla"):
    """``make_sparse_step_bucketed`` as it was before plans (cb591e5)."""
    from flinkml_tpu import kernels
    from flinkml_tpu.models._linear_sgd import (
        _acc_dt, _margin_grad, _soft_threshold, _window, ell_matvec)

    def step(coef, epoch, *rest):
        *blocks, learning_rate, reg_l2, reg_l1 = rest
        acc = _acc_dt(coef.dtype)
        contribs, flat_idx = [], []
        loss_l = jnp.zeros((), acc)
        wsum_l = jnp.zeros((), acc)
        for b, local_bs in enumerate(local_bss):
            idxl, vall, yl, wl = blocks[4 * b : 4 * (b + 1)]
            ib = _window(idxl, epoch, local_bs)
            vb = _window(vall, epoch, local_bs)
            yb = _window(yl, epoch, local_bs)
            wb = _window(wl, epoch, local_bs)
            dot = ell_matvec(ib, vb, coef)
            mult, per_ex = _margin_grad(loss, dot, yb, wb)
            contribs.append((vb * mult[:, None]).reshape(-1))
            flat_idx.append(ib.reshape(-1))
            loss_l = loss_l + jnp.sum(per_ex.astype(acc))
            wsum_l = wsum_l + jnp.sum(wb.astype(acc))
        grad_local = kernels.segment_sum(
            jnp.concatenate(contribs), jnp.concatenate(flat_idx),
            dim, backend=segsum_backend,
        )
        grad = jax.lax.psum(grad_local, axis)
        loss_sum = jax.lax.psum(loss_l, axis)
        wsum = jax.lax.psum(wsum_l, axis)
        grad = grad + 2.0 * reg_l2 * coef
        loss_sum = loss_sum + reg_l2 * jnp.sum(jnp.square(coef.astype(acc)))
        step_size = learning_rate.astype(acc) / wsum
        new_coef = _soft_threshold(
            coef - step_size.astype(coef.dtype) * grad,
            step_size.astype(coef.dtype) * reg_l1,
        )
        return new_coef, (loss_sum / wsum).astype(coef.dtype)

    return step


@pytest.mark.parametrize("widths", [(5,), (3, 7, 12)])
@pytest.mark.parametrize("loss", ["logistic", "hinge", "squared"])
def test_the_trainer_under_the_empty_plan_lowers_to_the_text_before_plans(
        mesh, loss, widths):
    m = mesh.mesh
    rep, rows = NamedSharding(m, P()), NamedSharding(m, P("data"))

    def arg(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    f32 = jnp.float32
    args = [arg((300,), f32), arg((), jnp.int32), arg((), f32)]
    for width in widths:
        args += [arg((128, width), jnp.int32, rows), arg((128, width), f32, rows),
                 arg((128,), f32, rows), arg((128,), f32, rows)]
    args += [arg((), f32)] * 4 + [arg((), jnp.int32)]
    sizes = (8,) * len(widths)
    now = _linear_sgd._sparse_trainer_bucketed(m, loss, sizes, "data", 300, "xla")
    before = _linear_sgd._whole_loop(
        m, _step_before_plans(loss, sizes, "data", 300), 4 * len(widths), "data",
        "lr_sparse_loop")
    text = now.lower(*args).as_text()
    assert text == before.lower(*args).as_text()
    assert "gather" in text and "dynamic_slice" not in text.replace(
        "stablehlo.dynamic_slice", "", len(widths) * 4)


def test_a_plan_is_part_of_the_trainers_cache_key_and_its_starts_are_not(mesh):
    def trainer(plan):
        return _linear_sgd._sparse_trainer_bucketed(
            mesh.mesh, "logistic", (BS,), "data", DIM, "xla", plan)

    assert trainer(()) is trainer(())
    assert trainer(MIXED_PLAN) is trainer(MIXED_PLAN)
    assert trainer(MIXED_PLAN) is not trainer(())
    indptr = np.arange(3001, dtype=np.int64) * 5
    plans = [_prepare(mesh, indptr, _field_rows(6, 3000, offset).reshape(-1),
                      PLAN_DIM + 384)
             for offset in (0, 384)]
    assert plans[0][0] == plans[1][0] == FIELD_PLAN
    assert not np.array_equal(plans[0][1][-1], plans[1][1][-1])
