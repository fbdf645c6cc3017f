"""``ALS.fit(Table)`` by target block (``models/_als_blocked.py``) against
the benchmark's plain float64 ALS-WR (``benchmark/reference/als.py``,
which imports nothing of the program), on seeded data at small sizes:

- whole trajectories of three iterations, explicit and implicit, rank 8
  and rank 100, within :data:`TOL`; the same fit with the factors a
  product reads rounded to bfloat16 (what one pass of a TPU's MXU makes
  of them) fails it;
- targets with no rating, one rating, more ratings than a chunk holds;
  pairs that come twice; ``regParam`` 0;
- the shares of eight devices against the one-device fit;
- what is kept with the ``Table``: a second fit uploads nothing, a new
  ``Table`` ingests again, the kept orders join the resident set and let
  go when a device reports no room; a fit equals its repeat to the bit;
- the ingest: vocabularies equal to ``np.unique``'s, both orders stable;
- the lane solver (``kernels/spd_solve``, interpreted) against NumPy's
  float64 solve;
- the rows fetched by ``kernels/row_fetch`` (interpreted) where the hot
  rows cover enough of the slots: half-steps and whole fits equal to the
  gather's to the bit, and where the choice falls.
"""

import gc
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import als as reference  # noqa: E402
from flinkml_tpu import table as table_mod  # noqa: E402
from flinkml_tpu.kernels import _mosaic, row_fetch, spd_solve  # noqa: E402
from flinkml_tpu.models import ALS, ALSModel, _als_blocked  # noqa: E402
from flinkml_tpu.parallel import DeviceMesh  # noqa: E402
from flinkml_tpu.table import Table  # noqa: E402
from flinkml_tpu.utils.metrics import metrics  # noqa: E402

#: Float32 sums and a float32 solve against float64, relative to a side's
#: largest factor: three iterations read 2e-6 to 3e-5 here (the solve's
#: error grows with the systems' condition; regParam 0.05 is the worst).
TOL = 2e-4
#: The fixed side rounded to bfloat16 reads 2e-3 and more.
ROUNDED = 10 * TOL


def _mesh(p: int = 1) -> DeviceMesh:
    return DeviceMesh(devices=jax.devices()[:p])


def _ratings(seed=0, users=60, items=40, nnz=3000, scale=5.0):
    """Seeded ratings with a heavy head of items (the cube), every id
    present at least once."""
    rng = np.random.default_rng(seed)
    u = np.concatenate([np.arange(users), rng.integers(0, users, nnz - users)])
    i = np.concatenate([np.arange(items),
                        (rng.random(nnz - items) ** 3 * items).astype(np.int64)])
    rng.shuffle(i)
    return (u.astype(np.int32), i.astype(np.int32),
            rng.uniform(0, scale, nnz).astype(np.float32))


def _table(u, i, r) -> Table:
    return Table({"user": u, "item": i, "rating": r})


def _als(rank=8, iters=3, reg=0.1, seed=1, mesh=None, implicit=False, alpha=1.0):
    return (ALS(mesh=mesh or _mesh()).set_rank(rank).set_max_iter(iters)
            .set_reg_param(reg).set_seed(seed).set_implicit_prefs(implicit)
            .set_alpha(alpha))


def _want(u, i, r, rank, iters, reg, seed, implicit=False, alpha=1.0):
    """The float64 trajectory from the program's own start factors, over
    vocabulary positions."""
    _, u = np.unique(u, return_inverse=True)
    item_ids, i = np.unique(i, return_inverse=True)
    start = np.asarray(_als_blocked.start_factors(seed, item_ids.size, rank))
    return reference.fit(u, i, r.astype(np.float64), start, iters, reg, implicit, alpha)


def _gap(model, want) -> float:
    return max(float(np.abs(got - ref).max() / np.abs(ref).max())
               for got, ref in zip(model.factors(), want))


@pytest.fixture
def small_chunks(monkeypatch):
    """Chunks of 256 slots: the tables here then have several chunks a
    bucket and targets cut in pieces."""
    monkeypatch.setattr(_als_blocked, "_CHUNK_SLOTS", 256)


# -- trajectories ---------------------------------------------------------------

@pytest.mark.parametrize("rank", [8, 100])
@pytest.mark.parametrize("implicit", [False, True], ids=["explicit", "implicit"])
def test_three_iterations_follow_the_float64_reference(rank, implicit, small_chunks):
    u, i, r = _ratings(seed=rank + implicit)
    model = _als(rank, implicit=implicit, alpha=0.5).fit(_table(u, i, r))
    want = _want(u, i, r, rank, 3, 0.1, 1, implicit, 0.5)
    assert model.factors()[0].dtype == np.float32
    assert _gap(model, want) < TOL


@pytest.mark.parametrize("rank", [8, 100])
@pytest.mark.parametrize("implicit", [False, True], ids=["explicit", "implicit"])
def test_a_fixed_side_rounded_to_bfloat16_fails_the_same_comparison(
        rank, implicit, small_chunks, monkeypatch):
    """What one bfloat16 pass of the Gram product makes of the factors it
    reads (on the CPU a precision changes nothing, so they are rounded)."""
    real = jnp.einsum

    def rounded(spec, a, b, **kw):
        low = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
        return real(spec, low(a), low(b), **kw)

    u, i, r = _ratings(seed=rank + implicit)
    monkeypatch.setattr(_als_blocked.jnp, "einsum", rounded)
    _als_blocked._program.cache_clear()
    try:
        model = _als(rank, implicit=implicit, alpha=0.5).fit(_table(u, i, r))
    finally:
        monkeypatch.undo()
        _als_blocked._program.cache_clear()
    assert _gap(model, _want(u, i, r, rank, 3, 0.1, 1, implicit, 0.5)) > ROUNDED


@pytest.mark.parametrize("reg", [0.0, 0.05, 1.4])
def test_the_regularisation_is_weighted_and_floored(reg, small_chunks):
    """``regParam`` 0 with targets of fewer ratings than the rank: the
    floor of 1e-4 keeps every system definite, in both."""
    u, i, r = _ratings(seed=3, users=30, items=200, nnz=900)
    model = _als(8, iters=1, reg=reg).fit(_table(u, i, r))
    want = _want(u, i, r, 8, 1, reg, 1)
    assert all(np.isfinite(f).all() for f in model.factors())
    # regParam 0: the first half-step's systems are conditioned like
    # 1 / 1e-4; float32 keeps three digits of them.
    assert _gap(model, want) < (TOL if reg else 0.05)


def test_pairs_that_come_twice_count_twice(small_chunks):
    u, i, r = _ratings(seed=4, nnz=1500)
    again = slice(0, 700)                 # these pairs come twice
    u2, i2, r2 = (np.concatenate([a, a[again]]) for a in (u, i, r))
    model = _als(8, iters=2).fit(_table(u2, i2, r2))
    assert _gap(model, _want(u2, i2, r2, 8, 2, 0.1, 1)) < TOL
    once = _als(8, iters=2).fit(_table(u, i, r))
    assert np.abs(once.factors()[0] - model.factors()[0]).max() > 1e-3


# -- the half-step on hand-made degrees -------------------------------------------

def _half_step(degrees, piece, rank=6, p=1, implicit=False, seed=0, hot_ids=None):
    """One half-step of targets with ``degrees`` ratings each over 50
    fixed rows, through :func:`plan_side` and the program (with
    ``hot_ids``: its rows fetched by ``kernels.row_fetch``, interpreted);
    and the float64 answer."""
    rng = np.random.default_rng(seed)
    degrees = np.asarray(degrees, np.int64)
    indptr = np.concatenate([[0], np.cumsum(degrees)])
    nnz, fixed_rows = int(indptr[-1]), 50
    other = rng.integers(0, fixed_rows, nnz).astype(np.int32)
    r = rng.uniform(0, 5, nnz).astype(np.float32)
    fixed = rng.normal(size=(fixed_rows, rank)).astype(np.float32)
    mesh = _mesh(p)
    plan = _als_blocked.plan_side(degrees, p, piece)
    with jax.enable_x64(False):
        side = _als_blocked._place_side(
            _als_blocked._Order(indptr, None),
            np.append(other, fixed_rows).astype(np.int32),
            np.append(r, 0).astype(np.float32), plan, mesh,
            None if hot_ids is None else (hot_ids, int((~np.isin(other, hot_ids)).sum())))
        table = mesh.replicate(jnp.pad(jnp.asarray(fixed),
                                       ((0, 1), (0, _als_blocked.LANES - rank))))
        program = _als_blocked._program(
            mesh.mesh, side.plan, rank, implicit, _als_blocked.GRAM_PRECISION, False,
            side.fetch_plan)
        padded, rows = program(*side.operands, table, np.float32(0.3), np.float32(0.5))
    want = reference.solve_targets(
        [(other[lo:hi], r[lo:hi]) for lo, hi in zip(indptr[:-1], indptr[1:])],
        fixed, 0.3, implicit, 0.5)
    return np.asarray(padded), np.asarray(rows), want, side


@pytest.mark.parametrize("degrees,piece", [
    ([0, 1, 2, 0, 7], 64),                    # no rating, one rating
    ([5, 300, 9, 64, 65, 1000, 3], 64),       # more than a chunk holds: cut in pieces
    ([64] * 9 + [1] * 20, 64),                # whole chunks and a short last one
    (list(range(1, 40)), 16),                 # every length of the ladder's foot
], ids=["none-and-one", "cut-in-pieces", "short-last-chunk", "ladder"])
@pytest.mark.parametrize("p", [1, 4])
def test_a_half_step_solves_every_target_over_all_its_ratings(degrees, piece, p):
    padded, rows, want, side = _half_step(degrees, piece, p=p)
    assert rows.shape == want.shape
    np.testing.assert_allclose(rows, want, rtol=0, atol=2e-5 * np.abs(want).max())
    # a target with no rating is 0; the next half-step's zero row is last
    assert not rows[np.asarray(degrees) == 0].any()
    assert not padded[-1].any() and not padded[:, rows.shape[1]:].any()
    np.testing.assert_array_equal(padded[:-1, :rows.shape[1]], rows)
    assert side.slots >= sum(degrees)


HALF_STEPS = [
    ([0, 1, 2, 0, 7], 64),                    # no rating, one rating
    ([5, 300, 9, 64, 65, 1000, 3], 64),       # more than a chunk holds: cut in pieces
    ([64] * 9 + [1] * 20, 64),                # whole chunks and a short last one
    (list(range(1, 40)), 16),                 # every length of the ladder's foot
    ([3000, 2500, 40, 7], 4096),              # chunks and pieces of several tiles
]


@pytest.mark.parametrize("hot", ["seven rows", "every row", "the zero row alone"])
@pytest.mark.parametrize("degrees,piece", HALF_STEPS, ids=[
    "none-and-one", "cut-in-pieces", "short-last-chunk", "ladder", "several-tiles"])
@pytest.mark.parametrize("p", [1, 4])
def test_a_half_step_through_the_fetch_kernel_is_the_gathers_to_the_bit(
        degrees, piece, p, hot):
    """The rows are copied, so the same products and the same solves: the
    padding's slots name the zero row (the hot rows' last), a turn's cold
    slots its own list of ids, whatever the hot rows are."""
    hot_ids = {"seven rows": np.r_[3, 11, 12, 20, 31, 40, 49, 50],
               "every row": np.r_[0:50, [50] * 14],
               "the zero row alone": np.full(8, 50)}[hot].astype(np.int32)
    _, gathered, want, plain = _half_step(degrees, piece, p=p)
    _, fetched, _, side = _half_step(degrees, piece, p=p, hot_ids=hot_ids)
    np.testing.assert_array_equal(fetched, gathered)
    assert plain.fetch_plan is None and plain.hot_slots == 0 and not plain.fetch
    hot_rows, cap = side.fetch_plan
    assert hot_rows == hot_ids.size and cap >= 8 and cap & (cap - 1) == 0
    cold, cold_at, starts, _ = side.fetch
    turns = sum(turns for _, turns in _als_blocked._loops(side.plan))
    assert cold_at.shape == (p * (turns + 1),) and cold.shape[0] % p == 0
    assert starts.shape == (p * sum(row_fetch.tiles_of(n) * turns
                                    for n, turns in _als_blocked._loops(side.plan)),)
    assert side.slots - sum(degrees) <= side.hot_slots <= side.slots
    if hot == "every row":
        assert side.hot_slots == side.slots
    if hot == "the zero row alone":
        assert side.hot_slots == side.slots - sum(degrees)


@pytest.fixture
def fetch_kernel_taken(monkeypatch):
    """The choice a TPU makes for a skewed table, here: the kernel runs
    interpreted."""
    monkeypatch.setattr(row_fetch, "unsupported_reason", lambda *a: None)


@pytest.mark.parametrize("implicit", [False, True], ids=["explicit", "implicit"])
@pytest.mark.parametrize("p", [1, 8])
def test_a_fit_through_the_fetch_kernel_is_the_gathers_fit_to_the_bit(
        p, implicit, small_chunks, monkeypatch):
    u, i, r = _ratings(seed=21)
    before = _als_counters()
    gathered = _als(8, implicit=implicit, mesh=_mesh(p)).fit(_table(u, i, r))
    between = _als_counters()
    monkeypatch.setattr(row_fetch, "unsupported_reason", lambda *a: None)
    fetched = _als(8, implicit=implicit, mesh=_mesh(p)).fit(_table(u, i, r))
    after = _als_counters()
    for a, b in zip(gathered.factors(), fetched.factors()):
        np.testing.assert_array_equal(a, b)
    assert _gap(fetched, _want(u, i, r, 8, 3, 0.1, 1, implicit)) < TOL
    # the gather serves no slot from fast memory; here every row is hot
    assert between.get("hot_slots", 0) == before.get("hot_slots", 0)
    assert (after["hot_slots"] - between.get("hot_slots", 0)
            == after["rating_slots"] - between["rating_slots"])
    # the cold slots' ids and the tiles' starts are part of what was sent
    assert (after["table_h2d_bytes"] - between["table_h2d_bytes"]
            > between["table_h2d_bytes"] - before.get("table_h2d_bytes", 0))


def test_eight_devices_through_the_fetch_kernel_are_the_one_device_fit(
        small_chunks, fetch_kernel_taken):
    u, i, r = _ratings(seed=6)
    one = _als(8).fit(_table(u, i, r))
    eight = _als(8, mesh=_mesh(8)).fit(_table(u, i, r))
    for a, b in zip(one.factors(), eight.factors()):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * np.abs(a).max())


@pytest.mark.parametrize("case,taken", [
    ("a CPU", False), ("skewed degrees", True), ("flat degrees", False),
    ("a short table", True)])
def test_the_hot_rows_follow_the_backend_and_the_degrees(monkeypatch, case, taken):
    """``hot_rows_of``: the heaviest rows and the zero row where they
    cover enough of the slots on a TPU, None everywhere else."""
    monkeypatch.delenv(_mosaic.ENV_INTERPRET_VAR, raising=False)
    if case != "a CPU":
        monkeypatch.setattr(_mosaic, "interpret_mode", lambda: False)
    rng = np.random.default_rng(3)
    rows = 40 if case == "a short table" else 400_000
    degrees = (np.full(rows, 12) if case == "flat degrees"
               else rng.permutation((3e6 / (np.arange(rows) + 10.0) ** 1.2).astype(np.int64)))
    slots = int(degrees.sum() * 1.2)
    hot = _als_blocked.hot_rows_of(degrees, slots)
    if not taken:
        assert hot is None
        return
    hot, cold_slots = hot
    assert cold_slots == degrees.sum() - degrees[hot[hot < rows]].sum()
    assert hot.dtype == np.int32 and hot.size == row_fetch.hot_rows(rows + 1)
    assert hot[-1] == rows                         # the zero row
    held = hot[hot < rows]
    assert np.unique(held).size == held.size == min(rows, hot.size - 1)
    assert degrees[held].min() >= np.sort(degrees)[::-1][held.size - 1]


def test_a_plan_holds_every_rating_once_and_no_chunk_passes_a_piece():
    rng = np.random.default_rng(5)
    degrees = (rng.random(500) ** 6 * 3000).astype(np.int64)
    for p in (1, 3):
        plan = _als_blocked.plan_side(degrees, p, 256)
        buckets, (pieces, cut), piece = plan.plan
        assert all(length * chunk <= max(piece, length) for length, chunk, _ in buckets)
        assert all(length <= piece for length, _, _ in buckets)
        order = _als_blocked._slot_order(
            _als_blocked._Order(np.concatenate([[0], np.cumsum(degrees)]), None),
            plan, p, int(degrees.sum()))
        held = order[order < degrees.sum()]
        np.testing.assert_array_equal(np.sort(held), np.arange(degrees.sum()))
        assert (degrees > piece).sum() == 0 or pieces > 0


# -- devices ------------------------------------------------------------------------

@pytest.mark.parametrize("implicit", [False, True], ids=["explicit", "implicit"])
def test_eight_devices_shares_are_the_one_device_fit(implicit, small_chunks):
    """Each device solves its own targets from the replicated fixed side
    and one all-gather joins them: the same sums in the same order a
    target, so the same factors (a chunk's batched product may round a
    sum's last bit another way)."""
    u, i, r = _ratings(seed=6)
    one = _als(8, implicit=implicit).fit(_table(u, i, r))
    eight = _als(8, implicit=implicit, mesh=_mesh(8)).fit(_table(u, i, r))
    for a, b in zip(one.factors(), eight.factors()):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * np.abs(a).max())
    assert _gap(eight, _want(u, i, r, 8, 3, 0.1, 1, implicit)) < TOL


# -- kept with the table ---------------------------------------------------------------

def _als_counters():
    return dict(metrics.group("als").snapshot()["counters"])


def test_a_second_fit_uploads_nothing_and_a_new_table_ingests_again():
    u, i, r = _ratings(seed=7)
    table = _table(u, i, r)
    before = _als_counters()
    first = _als(reg=0.1).fit(table)
    after_first = _als_counters()
    second = _als(reg=0.7).fit(table)          # another regParam: an operand
    again = _als(reg=0.1).fit(table)
    after = _als_counters()
    assert after_first["table_uploads"] - before.get("table_uploads", 0) == 1
    assert after_first["table_h2d_bytes"] > before.get("table_h2d_bytes", 0)
    assert after["table_uploads"] == after_first["table_uploads"]
    assert after["table_h2d_bytes"] == after_first["table_h2d_bytes"]
    assert after["fits"] - before.get("fits", 0) == 3
    assert after["half_steps"] - before.get("half_steps", 0) == 18
    assert after["ratings"] - before.get("ratings", 0) == 18 * u.size
    assert after["rating_slots"] - after["padding_slots"] == after["ratings"]
    # a fit is a function of (table, seed, hyper-parameters), to the bit
    for a, b in zip(first.factors(), again.factors()):
        np.testing.assert_array_equal(a, b)
    assert np.abs(first.factors()[0] - second.factors()[0]).max() > 1e-3
    fresh = _als(reg=0.1).fit(table.select(*table.column_names))
    assert _als_counters()["table_uploads"] == after["table_uploads"] + 1
    for a, b in zip(first.factors(), fresh.factors()):
        np.testing.assert_array_equal(a, b)


def test_the_key_names_columns_mesh_and_dtype_not_hyper_parameters():
    u, i, r = _ratings(seed=8)
    table = _table(u, i, r)
    _als(rank=8, reg=0.1, seed=1).fit(table)
    _als(rank=4, reg=0.5, seed=2, iters=1, implicit=True).fit(table)
    keys = [k for k in table._device_cache if isinstance(k, tuple)]
    assert len(keys) == 1 and keys[0][0] == "als_orders_on_mesh"
    assert keys[0][1:4] == ("user", "item", "rating")
    _als(rank=8, mesh=_mesh(2)).fit(table)     # another mesh: another placement
    assert len([k for k in table._device_cache if isinstance(k, tuple)]) == 2


def test_the_kept_orders_join_the_resident_set_and_let_go(monkeypatch):
    """A device that reports no room: the least recently used table's
    orders go, and its next fit places them again."""
    def used(device):
        return sum(a.nbytes // len(a.devices()) for a in jax.live_arrays()
                   if device in a.devices())

    gauge = lambda: metrics.group("hostdata").snapshot()["gauges"][
        "placement_kept_bytes"]
    tables = [_table(*_ratings(seed=s)) for s in (9, 10)]
    gc.collect()
    _als().fit(tables[0])
    kept = gauge()
    placed = next(v for k, v in tables[0]._device_cache.items() if isinstance(k, tuple))
    assert kept >= sum(a.nbytes for a in jax.tree_util.tree_leaves(placed)
                       if isinstance(a, jax.Array))
    base = {d: used(d) for d in jax.devices()}
    # room for half of one more such placement beside what is there
    monkeypatch.setattr(table_mod, "_free_bytes",
                        lambda d: base[d] + int(kept) // 2 - used(d))
    monkeypatch.setattr(_als_blocked, "_free_bytes", lambda d: None)
    evicted = metrics.group("hostdata").snapshot()["counters"].get(
        "placement_evictions", 0)
    _als().fit(tables[1])
    assert not [k for k in tables[0]._device_cache if isinstance(k, tuple)]
    assert metrics.group("hostdata").snapshot()["counters"][
        "placement_evictions"] == evicted + 1
    uploads = _als_counters()["table_uploads"]
    _als().fit(tables[0])
    assert _als_counters()["table_uploads"] == uploads + 1
    del tables, placed
    gc.collect()


def test_the_chunk_follows_what_the_device_reports_free(monkeypatch):
    device = jax.devices()[0]
    assert _als_blocked.chunk_slots([device], 0) == _als_blocked._CHUNK_SLOTS  # a CPU
    monkeypatch.setattr(_als_blocked, "_free_bytes", lambda d: 9 << 30)
    assert _als_blocked.chunk_slots([device], 5 << 30) == 1 << 14
    assert _als_blocked.chunk_slots([device], 0) == 1 << 15
    monkeypatch.setattr(_als_blocked, "_free_bytes", lambda d: 15 << 30)
    assert _als_blocked.chunk_slots([device], 5 << 30) == 1 << 15
    assert _als_blocked.chunk_slots([device], 20 << 30) == 1 << 12


# -- ingest ------------------------------------------------------------------------------

@pytest.mark.parametrize("ids", [
    np.array([5, 3, 3, 9, 5, 7], np.int32),                       # gaps
    np.array([-4, 2, -4, 0, 11, 2], np.int64),                     # negatives, int64
    np.arange(40, dtype=np.int32)[::-1].repeat(3),                 # every id, none missing
    np.array([0, 2 ** 40, 7, 2 ** 40], np.int64),                  # a span no table holds
    np.array(["b", "a", "c", "a"]),                                # strings
    np.array([1.5, 0.25, 1.5]),                                    # floats
], ids=["gaps", "negative-int64", "dense", "sparse-span", "strings", "floats"])
def test_a_vocabulary_is_np_uniques(ids):
    want_ids, want_index = np.unique(ids, return_inverse=True)
    got_ids, got_index = _als_blocked.vocabulary(ids)
    np.testing.assert_array_equal(got_ids, want_ids)
    assert got_ids.dtype == want_ids.dtype and got_index.dtype == np.int32
    np.testing.assert_array_equal(got_index, want_index.reshape(-1))


@pytest.mark.parametrize("grouped", [False, True], ids=["shuffled", "grouped"])
def test_an_order_is_the_stable_sort(grouped):
    rng = np.random.default_rng(12)
    index = (rng.random(70_000) ** 3 * 300).astype(np.int32)
    if grouped:
        index.sort()
    order = _als_blocked.group(index, 301)          # target 300 holds nothing
    want = np.argsort(index, kind="stable")
    assert (order.order is None) == grouped
    np.testing.assert_array_equal(
        order.indptr, np.searchsorted(index[want], np.arange(302)))
    if not grouped:
        np.testing.assert_array_equal(order.order, want)


def test_string_ids_fit_and_come_back_in_the_model():
    u, i, r = _ratings(seed=13, users=20, items=15, nnz=400)
    names = np.array([f"u{n:02d}" for n in range(20)])[u]
    model = _als(4, iters=2).fit(_table(names, i.astype(np.int64) * 7 - 3, r))
    want = _want(u, i, r, 4, 2, 0.1, 1)
    assert _gap(model, want) < TOL
    users_t, items_t = model.get_model_data()
    np.testing.assert_array_equal(users_t.column("id"), np.unique(names))
    np.testing.assert_array_equal(items_t.column("id"), np.unique(i) * 7 - 3)
    assert users_t.column("factors").dtype == np.float64      # widened when asked for
    assert model.user_factors.dtype == np.float64
    np.testing.assert_array_equal(model.user_factors, model.factors()[0])


def test_an_empty_table_is_refused():
    empty = _table(np.zeros(0, np.int32), np.zeros(0, np.int32), np.zeros(0, np.float32))
    with pytest.raises(ValueError, match="empty"):
        _als().fit(empty)


def test_the_table_fit_reads_no_environment(monkeypatch):
    """``FLINKML_TPU_ALS_REDUCTION`` gates the streamed formulation's
    reduction alone: a value it would refuse changes nothing here."""
    monkeypatch.setenv("FLINKML_TPU_ALS_REDUCTION", "no-such-layout")
    u, i, r = _ratings(seed=14, nnz=500)
    assert isinstance(_als(iters=1).fit(_table(u, i, r)), ALSModel)


# -- the lane solver -------------------------------------------------------------------------

@pytest.mark.parametrize("k", [3, 8, 21, 100])
def test_the_lane_solver_against_float64(k):
    """``kernels.spd_solve`` interpreted: the residual of its float32
    elimination against NumPy's float64 solve, on systems conditioned
    like ALS-WR's (a Gram of 3 k rows plus 0.1 I)."""
    rng = np.random.default_rng(k)
    batch = 2 * spd_solve.LANES
    y = rng.normal(size=(batch, 3 * k, k)) / np.sqrt(k)
    a = np.einsum("nlk,nlm->nkm", y, y) + 0.1 * np.eye(k)
    b = rng.normal(size=(batch, k))
    aug = np.zeros((k, spd_solve.augmented_width(k), batch), np.float32)
    aug[:, :k], aug[:, k] = a.transpose(1, 2, 0), b.T
    x = np.asarray(spd_solve.solve_lanes(jnp.asarray(aug), k, interpret=True)).T
    want = np.linalg.solve(a, b[..., None])[..., 0]
    assert np.abs(x - want).max() < 5e-6 * np.abs(want).max()
    residual = np.einsum("nkm,nm->nk", a, x.astype(np.float64)) - b
    assert np.abs(residual).max() < 2e-5 * np.abs(b).max()


def test_the_programs_solve_is_the_lane_solvers(monkeypatch):
    """``_solve`` on lanes (what a TPU runs), a batch that is not whole
    blocks: the identity fills them and is cut off again."""
    rng = np.random.default_rng(15)
    k, n = 5, 7
    y = rng.normal(size=(n, 12, k))
    a = np.einsum("nlk,nlm->nkm", y, y) + np.eye(k)
    b = rng.normal(size=(n, k))
    aug = np.zeros((n, k, spd_solve.augmented_width(k)), np.float32)
    aug[:, :, :k], aug[:, :, k] = a, b
    with jax.enable_x64(False):
        on_lanes = np.asarray(_als_blocked._solve(jnp.asarray(aug), k, True))
        by_xla = np.asarray(_als_blocked._solve(jnp.asarray(aug), k, False))
    want = np.linalg.solve(a, b[..., None])[..., 0]
    assert on_lanes.shape == (n, k)
    np.testing.assert_allclose(on_lanes, want, rtol=0, atol=1e-5 * np.abs(want).max())
    np.testing.assert_allclose(by_xla, want, rtol=0, atol=1e-5 * np.abs(want).max())
