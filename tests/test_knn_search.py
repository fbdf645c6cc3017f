"""The KNN search (PR 30) against ``benchmark/reference/knn.py`` (NumPy
float64, direct sums), index for index: tiles that do not divide the
rows, planted exact ties, products rounded to bfloat16 shown to differ,
four row shares merged into the whole; and the model data's one upload."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import knn as reference
from flinkml_tpu.models import Knn, KnnModel, knn
from flinkml_tpu.table import Table
from flinkml_tpu.utils.metrics import metrics

#: More than float32 rounding moves a squared distance by here (rows of
#: squared length up to ~ dim / 3, dim <= 33: a few 1e-6), far less than
#: the gaps between a few hundred random rows.
TOL = 2e-5


def _levels(rng, rows, dim):
    """Pixel-like float32 rows: ``level / 255`` (NOT exact in bfloat16)."""
    return (rng.integers(0, 256, (rows, dim)) / 255).astype(np.float32)


def _small_integers(rng, rows, dim):
    """Rows of 0..3: every distance is a small integer, exact at any
    precision, and most queries have ties at the k-th place."""
    return rng.integers(0, 4, (rows, dim)).astype(np.float32)


def _assert_same_neighbours(got, want, want_d2, k):
    """The same SET of neighbours wherever the k-th and the (k + 1)-th
    are decided, the same ORDER wherever every place among them is;
    returns the two shares."""
    stable = ~reference.unstable(want_d2, k, TOL)
    np.testing.assert_array_equal(np.sort(got[stable], axis=1),
                                  np.sort(want[stable, :k], axis=1))
    ordered = np.diff(want_d2, axis=1).min(axis=1) > 2 * TOL
    np.testing.assert_array_equal(got[ordered], want[ordered, :k])
    return stable.mean(), ordered.mean()


def _search(queries, train, k, tile, chunk=64):
    x = jnp.asarray(train, jnp.float32)
    run = jax.jit(functools.partial(knn.nearest, k=k, chunk=chunk, tile=tile,
                                    precision=knn.PRODUCT_PRECISION))
    d2, rows = run(jnp.asarray(queries, jnp.float32), x, jnp.sum(x * x, axis=-1))
    return np.asarray(d2), np.asarray(rows)


SHAPES = [  # rows, dim, k, tile: the tile never divides the rows
    (1000, 16, 5, 384), (777, 33, 1, 256), (300, 8, 7, 300),
    (513, 20, 5, 128), (2050, 12, 130, 512), (90, 5, 5, 128),
]


@pytest.mark.parametrize("rows,dim,k,tile", SHAPES)
def test_neighbours_equal_the_float64_reference(rng, rows, dim, k, tile):
    train, queries = _levels(rng, rows, dim), _levels(rng, 70, dim)
    tile = min(tile, rows)
    d2, got = _search(queries, train, k, tile)
    want, want_d2 = reference.k_nearest(queries, train, k)
    stable, ordered = _assert_same_neighbours(got, want, want_d2, k)
    assert stable > 0.9 and (ordered > 0.9 or k > 7)
    np.testing.assert_allclose(d2, want_d2[:, :k], atol=TOL)


def test_the_kernel_and_the_sort_rank_a_tile_alike(rng, monkeypatch):
    """A TPU ranks a tile with the Pallas kernel, every other backend
    with ``lax.top_k``: the search returns the same rows and distances,
    bit for bit, with either (the kernel interpreted here), ties
    included."""
    from flinkml_tpu.kernels import _gate, topk

    assert _gate.interpret_mode()          # so this suite runs lax.top_k
    train = _small_integers(rng, 700, 6)
    train[400:] = train[:300]
    queries = _small_integers(rng, 40, 6)
    sorted_d2, sorted_rows = _search(queries, train, 5, 256)

    def by_kernel(d2, k):
        neg, at = topk.pallas_top_k(-d2, k, interpret=True)
        return -neg, at

    monkeypatch.setattr(knn, "_tile_top_k", by_kernel)
    kernel_d2, kernel_rows = _search(queries, train, 5, 256)
    np.testing.assert_array_equal(kernel_rows, sorted_rows)
    np.testing.assert_array_equal(kernel_d2, sorted_d2)


@pytest.mark.parametrize("rows,dim,k,tile", SHAPES)
def test_exact_ties_go_to_the_lower_row(rng, rows, dim, k, tile):
    """Integer rows (every product exact, ties at most k-th places) with
    a third of the rows planted again further down: the duplicate's
    distance is the original's, bit for bit, in another tile."""
    train = _small_integers(rng, rows, dim)
    train[2 * rows // 3:] = train[:rows - 2 * rows // 3]
    queries = _small_integers(rng, 70, dim)
    d2, got = _search(queries, train, k, min(tile, rows))
    want, want_d2 = reference.k_nearest(queries, train, k)
    assert (want_d2[:, k - 1] == want_d2[:, k]).mean() > 0.3  # ties at the edge
    np.testing.assert_array_equal(got, want[:, :k])
    np.testing.assert_array_equal(d2, want_d2[:, :k])


def _near_one_image(rng, rows, dim, base):
    """Rows up to ten levels off one image: neighbours as close together as
    in a dense archive, where a product's rounding decides the order."""
    return (np.clip(base + rng.integers(-10, 11, (rows, dim)), 0, 255)
            / 255).astype(np.float32)


@pytest.mark.parametrize("rows,dim,k,tile", [
    (2000, 64, 5, 384), (1501, 96, 1, 256), (2500, 48, 7, 1024)])
def test_products_in_bfloat16_fail_the_same_comparison(rng, rows, dim, k, tile):
    """What one bfloat16 pass of the MXU computes: both operands rounded
    to bfloat16, float32 sums. The neighbours then differ from the
    reference's for many queries the reference calls stable, on rows the
    float32 search ranks as the reference does."""
    base = rng.integers(0, 256, dim)
    train = _near_one_image(rng, rows, dim, base)
    queries = _near_one_image(rng, 70, dim, base)
    _, sound = _search(queries, train, k, min(tile, rows))
    low = lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))
    assert not np.array_equal(low(train), train)
    want, want_d2 = reference.k_nearest(queries, train, k)
    _, got = _search(low(queries), low(train), k, min(tile, rows))
    stable, _ = _assert_same_neighbours(sound, want, want_d2, k)
    assert stable > 0.5
    stable = ~reference.unstable(want_d2, k, TOL)
    wrong = (np.sort(got[stable], axis=1) != np.sort(want[stable, :k], axis=1)).any(axis=1)
    assert wrong.sum() >= 3        # the comparison allows none


@pytest.mark.parametrize("maker", [_levels, _small_integers])
def test_four_shares_merged_are_the_whole(rng, maker):
    """The cell's cut tied to the whole: each of four row shares searched
    alone (the program, one share a chip), their [queries, k] answers
    merged by (distance, global row), equal the unsharded reference."""
    rows, dim, k = 1203, 16, 5
    train, queries = maker(rng, rows, dim), maker(rng, 50, dim)
    bounds = np.linspace(0, rows, 5).astype(int)
    found = [_search(queries, train[lo:hi], k, 128)
             for lo, hi in zip(bounds[:-1], bounds[1:])]
    merged, merged_d2 = reference.merge_shares(
        [r + lo for (_, r), lo in zip(found, bounds[:-1])],
        [d for d, _ in found], k)
    want, want_d2 = reference.k_nearest(queries, train, k)
    stable, _ = _assert_same_neighbours(merged, want, want_d2, k)
    if maker is _small_integers:
        assert stable < 0.9             # ties: decided by the row alone
        np.testing.assert_array_equal(merged, want[:, :k])


def test_reference_shortlist_and_vote(rng):
    train, queries = _levels(rng, 3000, 24), _levels(rng, 40, 24)
    labels = rng.integers(0, 4, 3000) * 2.5
    direct = reference.k_nearest(queries, train, 5)
    reference_block = reference.BLOCK_ROWS
    reference.BLOCK_ROWS = 700
    try:
        short = reference.k_nearest(queries, train, 5, shortlist=64)
    finally:
        reference.BLOCK_ROWS = reference_block
    np.testing.assert_array_equal(short[0], direct[0])
    np.testing.assert_allclose(short[1], direct[1], rtol=1e-13)
    # ties of the vote go to the smaller class: two of 0.0, two of 5.0
    rows = np.array([[0, 1, 2, 3, 4]])
    assert reference.vote(np.array([5.0, 0.0, 5.0, 0.0, 2.5]), rows, 5)[0] == 0.0
    assert reference.vote(np.array([5.0, 0.0, 5.0, 0.0, 5.0]), rows, 5)[0] == 5.0
    d2 = np.array([[1.0, 2.0, 2.0 + 1e-7], [1.0, 2.0, 3.0]])
    np.testing.assert_array_equal(reference.unstable(d2, 2, 1e-6), [True, False])
    np.testing.assert_array_equal(reference.unstable(d2[:, :2], 2, 1e-6), [False, False])


def test_model_predicts_the_references_vote(rng):
    train, queries = _levels(rng, 900, 16), _levels(rng, 120, 16)
    labels = (rng.integers(0, 5, 900) * 3).astype(np.float32)
    model = Knn().set_k(6).fit(Table({"features": train, "label": labels}))
    (out,) = model.transform(Table({"features": queries}))
    rows, d2 = reference.k_nearest(queries, train, 6)
    stable = ~reference.unstable(d2, 6, TOL)
    np.testing.assert_array_equal(
        out["prediction"][stable], reference.vote(labels, rows, 6)[stable])


def test_float32_columns_reach_the_model_as_they_are(rng):
    train = _levels(rng, 200, 8)
    labels = rng.integers(0, 3, 200).astype(np.float32)
    model = Knn().fit(Table({"features": train, "label": labels}))
    (data,) = model.get_model_data()
    assert data.column("features") is train          # no float64 copy
    assert np.shares_memory(data.column("labels"), labels)
    model.transform(Table({"features": train[:5]}))
    assert model._resident.features.dtype == jnp.float32
    assert model._resident.features.shape == train.shape
    # object columns of Vectors still work
    from flinkml_tpu.linalg import DenseVector

    vectors = np.empty(200, dtype=object)
    vectors[:] = [DenseVector(r) for r in train.astype(np.float64)]
    other = Knn().fit(Table({"features": vectors, "label": labels}))
    np.testing.assert_array_equal(
        other.transform(Table({"features": train[:20]}))[0]["prediction"],
        model.transform(Table({"features": train[:20]}))[0]["prediction"])


LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def test_second_transform_uploads_no_model_and_compiles_nothing(rng, monkeypatch):
    train, queries = _levels(rng, 500, 12), _levels(rng, 64, 12)
    labels = rng.integers(0, 3, 500).astype(np.float64)
    model = Knn().fit(Table({"features": train, "label": labels}))
    group = metrics.group("knn")
    before = group.snapshot()["counters"]
    spans_before = metrics.group("span").snapshot()["counters"]
    uniques = []
    real_unique = np.unique
    monkeypatch.setattr(np, "unique", lambda *a, **k: (
        uniques.append(1), real_unique(*a, **k))[1])
    lowered = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_, **__: lowered.append(name) if name == LOWERED else None)

    model.transform(Table({"features": queries}))
    first = group.snapshot()["counters"]
    assert first["model_uploads"] - before.get("model_uploads", 0) == 1
    sent = first["model_h2d_bytes"] - before.get("model_h2d_bytes", 0)
    assert sent == 500 * 12 * 4 + 500 * 4
    assert len(uniques) == 1
    del lowered[:]
    (out,) = model.transform(Table({"features": queries[::-1].copy()}))
    second = group.snapshot()["counters"]
    assert second["model_h2d_bytes"] == first["model_h2d_bytes"]
    assert second["model_uploads"] == first["model_uploads"]
    assert second["query_rows"] - first["query_rows"] == 64
    assert second["train_tiles"] - first["train_tiles"] == 1
    assert len(uniques) == 1 and lowered == []
    spans = metrics.group("span").snapshot()["counters"]
    moved = lambda name: spans[name] - spans_before.get(name, 0)
    assert moved("knn.model_to_device.calls") == 1
    assert moved("knn.model_to_device.bytes") == sent
    assert moved("knn.search.calls") == moved("knn.dispatch.calls") == 2
    assert moved("knn.readback.calls") == 2
    assert moved("knn.search.seconds") >= moved("knn.dispatch.seconds") > 0
    # new model data is placed again
    model.set_model_data(Table({"features": train[:100], "labels": labels[:100]}))
    model.transform(Table({"features": queries}))
    assert group.snapshot()["counters"]["model_uploads"] == second["model_uploads"] + 1


def test_save_load_and_model_data_round_trip(tmp_path, rng):
    train, queries = _levels(rng, 300, 10), _levels(rng, 30, 10)
    labels = rng.integers(0, 4, 300).astype(np.float32)
    model = Knn().set_k(3).fit(Table({"features": train, "label": labels}))
    want = model.transform(Table({"features": queries}))[0]["prediction"]
    model.save(str(tmp_path / "knn"))
    loaded = KnnModel.load(str(tmp_path / "knn"))
    assert loaded.get_k() == 3
    assert loaded.get_model_data()[0].column("features").dtype == np.float32
    np.testing.assert_array_equal(
        loaded.transform(Table({"features": queries}))[0]["prediction"], want)
    other = KnnModel().set_k(3).set_model_data(*model.get_model_data())
    np.testing.assert_array_equal(
        other.transform(Table({"features": queries}))[0]["prediction"], want)


def test_k_is_held_to_the_rows_there_are(rng):
    """Reference parity: with k > n every row votes (``KnnModel``'s
    queue holds them all); with more rows than one tile the tile grows
    to hold k."""
    train = _levels(rng, 40, 6)
    labels = np.array([0.0] * 15 + [1.0] * 25)
    model = Knn().set_k(1000).fit(Table({"features": train, "label": labels}))
    np.testing.assert_array_equal(
        model.transform(Table({"features": train[:4]}))[0]["prediction"], [1.0] * 4)
    assert knn._tile_rows(40, 40) == 40
    assert knn._tile_rows(10 ** 6, 5) == knn.TRAIN_TILE
    assert knn._tile_rows(10 ** 6, 40_000) == 40_064
    assert knn._chunk_rows(10_000, 4096) == 3336
    assert knn._chunk_rows(5, 4096) == 8
