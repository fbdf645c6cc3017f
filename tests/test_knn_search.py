"""The KNN search (PR 30) against ``benchmark/reference/knn.py`` (NumPy
float64, direct sums), index for index: tiles that do not divide the
rows, planted exact ties, products rounded to bfloat16 shown to differ,
four row shares merged into the whole; and the model data's one upload.
Both searches (PR 31): the tiled XLA one, which this backend runs, and
the fused product-and-ranking kernel a TPU runs, interpreted here at
blocks small enough to cut these searches into many. Since PR 35 the
kernel forms its float32 product from bfloat16 parts it makes itself,
one long contraction, train blocks outermost: the parts, the product at
widths that do and do not fill their tiles, and the new loop order."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import knn as reference
from flinkml_tpu.kernels import knn_search
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


def _tiled(queries, train, k, tile, chunk=64, precision=knn.PRODUCT_PRECISION):
    """The tiled XLA search, as every backend but a TPU runs it."""
    x = jnp.asarray(train, jnp.float32)
    run = jax.jit(functools.partial(knn.nearest, k=k, chunk=chunk,
                                    tile=min(tile, train.shape[0]),
                                    precision=precision))
    d2, rows = run(jnp.asarray(queries, jnp.float32), x, jnp.sum(x * x, axis=-1))
    return np.asarray(d2), np.asarray(rows)


def _fused(queries, train, k, tile, chunk=24, precision=knn.PRODUCT_PRECISION,
           chunk_blocks=2):
    """The fused kernel, interpreted: train blocks of ``tile`` rows up to
    whole lanes, query blocks of at most ``chunk`` rows, ``chunk_blocks``
    of them sharing a train block's parts (70 queries are three blocks of
    24, the last one padded, in two chunks of two, the last block all
    padding)."""
    x = jnp.asarray(train, jnp.float32)
    run = jax.jit(functools.partial(
        knn_search.fused_nearest, k=k, precision=precision, query_block=chunk,
        train_block=-(-tile // knn_search.LANES) * knn_search.LANES,
        query_blocks=chunk_blocks, interpret=True))
    d2, rows = run(jnp.asarray(queries, jnp.float32), x, jnp.sum(x * x, axis=-1))
    return np.asarray(d2), np.asarray(rows)


SHAPES = [  # rows, dim, k, tile: the tile never divides the rows
    (1000, 16, 5, 384), (777, 33, 1, 256), (300, 8, 7, 300),
    (513, 20, 5, 128), (2050, 12, 130, 512), (90, 5, 5, 128),
]
# The kernel's: k 1, 5 and 8; a partial last block; fewer rows than one
# block (90, 100); query rows that do not fill their blocks.
FUSED_SHAPES = [
    (1000, 16, 5, 384), (777, 33, 1, 256), (300, 8, 8, 300),
    (513, 20, 5, 128), (2050, 12, 8, 512), (90, 5, 5, 128), (100, 7, 1, 256),
]
BOTH = ([pytest.param(_tiled, *shape, id="tiled-%d-%d-%d-%d" % shape)
         for shape in SHAPES]
        + [pytest.param(_fused, *shape, id="fused-%d-%d-%d-%d" % shape)
           for shape in FUSED_SHAPES])


@pytest.mark.parametrize("search,rows,dim,k,tile", BOTH)
def test_neighbours_equal_the_float64_reference(rng, search, rows, dim, k, tile):
    train, queries = _levels(rng, rows, dim), _levels(rng, 70, dim)
    d2, got = search(queries, train, k, tile)
    want, want_d2 = reference.k_nearest(queries, train, k)
    stable, ordered = _assert_same_neighbours(got, want, want_d2, k)
    assert stable > 0.9 and (ordered > 0.9 or k > 7)
    np.testing.assert_allclose(d2, want_d2[:, :k], atol=TOL)


def test_the_kernel_and_the_sort_rank_a_tile_alike(rng, monkeypatch):
    """A TPU ranks a tile with the Pallas kernel, every other backend
    with ``lax.top_k``: the search returns the same rows and distances,
    bit for bit, with either (the kernel interpreted here), ties
    included."""
    from flinkml_tpu.kernels import _mosaic, topk

    assert _mosaic.interpret_mode()          # so this suite runs lax.top_k
    train = _small_integers(rng, 700, 6)
    train[400:] = train[:300]
    queries = _small_integers(rng, 40, 6)
    sorted_d2, sorted_rows = _tiled(queries, train, 5, 256)

    def by_kernel(d2, k):
        neg, at = topk.pallas_top_k(-d2, k, interpret=True)
        return -neg, at

    monkeypatch.setattr(knn, "_tile_top_k", by_kernel)
    kernel_d2, kernel_rows = _tiled(queries, train, 5, 256)
    np.testing.assert_array_equal(kernel_rows, sorted_rows)
    np.testing.assert_array_equal(kernel_d2, sorted_d2)


@pytest.mark.parametrize("search,rows,dim,k,tile", BOTH)
def test_exact_ties_go_to_the_lower_row(rng, search, rows, dim, k, tile):
    """Integer rows (every product exact, ties at most k-th places) with
    a third of the rows planted again further down: the duplicate's
    distance is the original's, bit for bit, in another tile or block."""
    train = _small_integers(rng, rows, dim)
    train[2 * rows // 3:] = train[:rows - 2 * rows // 3]
    queries = _small_integers(rng, 70, dim)
    d2, got = search(queries, train, k, tile)
    want, want_d2 = reference.k_nearest(queries, train, k)
    assert (want_d2[:, k - 1] == want_d2[:, k]).mean() > 0.3  # ties at the edge
    np.testing.assert_array_equal(got, want[:, :k])
    np.testing.assert_array_equal(d2, want_d2[:, :k])


def _near_one_image(rng, rows, dim, base):
    """Rows up to ten levels off one image: neighbours as close together as
    in a dense archive, where a product's rounding decides the order."""
    return (np.clip(base + rng.integers(-10, 11, (rows, dim)), 0, 255)
            / 255).astype(np.float32)


@pytest.mark.parametrize("search", [_tiled, _fused])
@pytest.mark.parametrize("rows,dim,k,tile", [
    (2000, 64, 5, 384), (1501, 96, 1, 256), (2500, 48, 7, 1024)])
def test_products_in_bfloat16_fail_the_same_comparison(rng, search, rows, dim, k, tile):
    """What one bfloat16 pass of the MXU computes: both operands rounded
    to bfloat16, float32 sums. The neighbours then differ from the
    reference's for many queries the reference calls stable, on rows the
    float32 search ranks as the reference does. The rounded operands go
    through at ``Precision.DEFAULT``, the static argument the benchmark's
    control gives (on this backend a product's precision changes
    nothing: the rounding is what fails)."""
    base = rng.integers(0, 256, dim)
    train = _near_one_image(rng, rows, dim, base)
    queries = _near_one_image(rng, 70, dim, base)
    _, sound = search(queries, train, k, tile)
    low = lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))
    assert not np.array_equal(low(train), train)
    want, want_d2 = reference.k_nearest(queries, train, k)
    _, got = search(low(queries), low(train), k, tile,
                    precision=jax.lax.Precision.DEFAULT)
    stable, _ = _assert_same_neighbours(sound, want, want_d2, k)
    assert stable > 0.5
    stable = ~reference.unstable(want_d2, k, TOL)
    wrong = (np.sort(got[stable], axis=1) != np.sort(want[stable, :k], axis=1)).any(axis=1)
    assert wrong.sum() >= 3        # the comparison allows none


def _centred(rng, rows, dim, order):
    """Train rows around one point, and queries at it: rows in no order,
    nearest first (after the first block no row enters: the screen alone
    runs) or farthest first (every block replaces all ``k``)."""
    centre = rng.integers(100, 156, dim)
    step = rng.permutation(rows) if order == "none" else np.arange(rows)
    if order == "farthest_first":
        step = step[::-1]
    # Row i lies 4 + step[i] / 2 levels off the centre in one pixel and a
    # little in the others: its distance grows with step[i], by more than
    # float32 rounding moves it.
    train = np.tile(centre, (rows, 1)).astype(np.float64)
    train[:, 0] += step * 0.5 + 4.0
    train[:, 1:] += rng.integers(0, 2, (rows, dim - 1)) * 1e-3
    queries = centre + rng.integers(0, 2, (70, dim)) * 1e-3
    return (train / 255).astype(np.float32), (queries / 255).astype(np.float32)


@pytest.mark.parametrize("k", [1, 5, 8])
@pytest.mark.parametrize("order", ["nearest_first", "farthest_first", "none"])
def test_the_fused_search_in_any_order_of_the_stream(rng, order, k):
    """The kernel against the tiled search, index for index, and both
    against the reference: 700 rows in blocks of 128 (the last one of
    60), where no later block holds an entrant, and where every block
    holds ``k``. The distances to float32's tolerance: the kernel's own
    six bfloat16 products and XLA's round differently (PR 35)."""
    train, queries = _centred(rng, 700, 9, order)
    d2, got = _fused(queries, train, k, 128)
    tiled_d2, tiled = _tiled(queries, train, k, 256)
    np.testing.assert_array_equal(got, tiled)
    np.testing.assert_allclose(d2, tiled_d2, atol=TOL)
    want, want_d2 = reference.k_nearest(queries, train, k)
    _assert_same_neighbours(got, want, want_d2, k)
    if order != "none":
        first = np.arange(k) if order == "nearest_first" else 699 - np.arange(k)
        stable = ~reference.unstable(want_d2, k, TOL)
        assert stable.mean() > 0.9
        np.testing.assert_array_equal(np.sort(got[stable], axis=1),
                                      np.tile(np.sort(first), (stable.sum(), 1)))


@pytest.mark.parametrize("k", [1, 5, 8])
def test_all_equal_distances_keep_the_first_rows(k):
    """Every row the same: every distance ties, and the ``k`` lowest rows
    are the answer, block after block (300 rows in blocks of 128)."""
    train = np.full((300, 6), 0.25, np.float32)
    queries = np.full((20, 6), 0.75, np.float32)
    for search in (_tiled, _fused):
        d2, got = search(queries, train, k, 128)
        np.testing.assert_array_equal(got, np.tile(np.arange(k), (20, 1)))
        np.testing.assert_array_equal(d2, np.full((20, k), 1.5, np.float32))


PARTS_OF = {
    "levels": lambda rng: _levels(rng, 64, 784),
    "normal": lambda rng: rng.normal(size=(64, 100)).astype(np.float32),
    "wide_range": lambda rng: (rng.choice([-1.0, 1.0], (64, 130))
                               * 2.0 ** rng.uniform(-20, 20, (64, 130))).astype(np.float32),
    "zeros": lambda rng: np.zeros((8, 16), np.float32),
    "negatives": lambda rng: -_levels(rng, 64, 33) - np.float32(1e-3),
}


@pytest.mark.parametrize("in_kernel", [True, False])
@pytest.mark.parametrize("values", sorted(PARTS_OF))
def test_three_bfloat16_parts_are_the_float32(rng, values, in_kernel):
    """``hi + mid + lo`` is the float32 again, bit for bit, jitted as the
    kernel's callers run it, with the roundings a kernel makes and with
    the ones its caller makes, and each part is what is left of the one
    before: nothing of a value is lost that a one-pass product would
    keep."""
    v = PARTS_OF[values](rng)
    split = functools.partial(knn_search.rounded_parts, in_kernel=in_kernel)
    hi, mid, lo = (np.asarray(p.astype(jnp.float32))
                   for p in jax.jit(split)(jnp.asarray(v)))
    np.testing.assert_array_equal((hi + mid) + lo, v)
    low = lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_array_equal(hi, low(v))
    np.testing.assert_array_equal(mid, low(v - hi))
    if values not in ("zeros",):
        assert np.abs(mid).max() > 0
    assert np.all(np.abs(mid) <= np.abs(hi) * 2.0 ** -7)
    assert np.all(np.abs(lo) <= np.abs(hi) * 2.0 ** -15)


def test_the_products_of_each_precision():
    """``precision`` keeps its two meanings: ``HIGHEST`` the six products a
    six-pass float32 contraction makes, the smallest first, ``DEFAULT`` one
    pass; anything else is ``jnp.dot``'s. The parts lie end to end in
    whole sublane tiles, the contraction comes up to whole MXU tiles: at
    ``d`` 784, 37 tiles where six contractions of 784 take 42."""
    P = jax.lax.Precision
    six = knn_search._products(P.HIGHEST)
    assert sorted(six) == [(0, 0), (0, 1), (0, 2), (1, 0), (1, 1), (2, 0)]
    assert [a + b for a, b in six] == sorted(a + b for a, b in six)[::-1]
    assert knn_search._products(P.DEFAULT) == ((0, 0),)
    assert knn_search._products(P.HIGH) is None
    assert knn_search._stacked_width(784, six) == (784, 37 * 128)
    assert knn_search._stacked_width(784, ((0, 0),)) == (784, 7 * 128)
    assert knn_search._stacked_width(100, six) == (112, 6 * 128)
    assert knn_search._stacked_width(16, six) == (16, 128)
    assert knn_search._stacked_width(5, six) == (16, 128)


# d: the cell's (49 sublane tiles, 6.125 lane tiles), under one lane tile
# and not whole sublane tiles (100), one lane tile and a remainder not
# whole sublane tiles (130), one sublane tile (16). Rows that do not fill
# the last train block, queries that do not fill their blocks, two query
# blocks a chunk and two chunks over several train blocks.
WIDTHS = [(784, 300, 128), (100, 700, 256), (130, 450, 128), (16, 1000, 384)]


@pytest.mark.parametrize("dim,rows,tile", WIDTHS)
@pytest.mark.parametrize("maker", [_levels, _small_integers])
def test_the_kernels_own_product_at_any_width(rng, maker, dim, rows, tile):
    """The kernel's distances at ``HIGHEST`` against float64 within
    float32's tolerance at the norms there are, its rows the reference's;
    on integer rows (every product exact, a third of the rows planted
    again further down: ties across blocks) bit for bit and to the lower
    row, in every query block of every chunk."""
    train, queries = maker(rng, rows, dim), maker(rng, 70, dim)
    if maker is _small_integers:
        train[2 * rows // 3:] = train[:rows - 2 * rows // 3]
    d2, got = _fused(queries, train, 5, tile)
    want, want_d2 = reference.k_nearest(queries, train, 5)
    if maker is _small_integers:
        np.testing.assert_array_equal(got, want[:, :5])
        np.testing.assert_array_equal(d2, want_d2[:, :5])
        return
    # 8 units in the last place of (|q| + |x|)^2, the benchmark's tolerance
    tol = 8 * 2.0 ** -24 * (2 * np.sqrt(dim / 3)) ** 2
    np.testing.assert_allclose(d2, want_d2[:, :5], atol=tol, rtol=0)
    stable = ~reference.unstable(want_d2, 5, tol)
    assert stable.mean() > 0.8
    np.testing.assert_array_equal(np.sort(got[stable], axis=1),
                                  np.sort(want[stable, :5], axis=1))


@pytest.mark.parametrize("chunk,chunk_blocks", [(24, 1), (24, 3), (16, 2), (72, 10)])
def test_every_query_block_sees_every_train_block_in_order(rng, chunk, chunk_blocks):
    """The loop's order (PR 35): train blocks outermost, the query blocks
    of a chunk inside. However the 70 queries are cut (three blocks in
    three chunks; in one chunk; five blocks in three chunks, the last one
    padding; one block), each block meets all six train blocks in
    ascending order: of equal rows planted in EVERY train block the lower
    comes first, and the answers are the tiled search's."""
    train = _small_integers(rng, 128, 12)
    train = np.concatenate([train] * 6)[:700]          # six blocks, the last of 60
    queries = _small_integers(rng, 70, 12)
    d2, got = _fused(queries, train, 5, 128, chunk=chunk, chunk_blocks=chunk_blocks)
    tiled_d2, tiled = _tiled(queries, train, 5, 256)
    np.testing.assert_array_equal(got, tiled)
    np.testing.assert_array_equal(d2, tiled_d2)
    # a row's copies in the later blocks tie with it: the lowest comes first
    assert (got[:, 0] < 128).all() and (np.diff(d2, axis=1) == 0).mean() > 0.5
    assert (np.diff(got, axis=1)[np.diff(d2, axis=1) == 0] > 0).all()
    want, _ = reference.k_nearest(queries, train, 5)
    np.testing.assert_array_equal(got, want[:, :5])


def test_another_precision_is_jnp_dots_own(rng):
    """``Precision.HIGH`` is neither of the two: the kernel hands it to
    ``jnp.dot`` on the float32 blocks, as every precision went before PR
    35 (a CPU computes it exactly)."""
    train, queries = _levels(rng, 300, 20), _levels(rng, 40, 20)
    d2, got = _fused(queries, train, 5, 128, precision=jax.lax.Precision.HIGH)
    want, want_d2 = reference.k_nearest(queries, train, 5)
    _assert_same_neighbours(got, want, want_d2, 5)
    np.testing.assert_allclose(d2, want_d2[:, :5], atol=TOL)


def test_train_blocks_fit_fast_memory():
    """The train block comes down from ``TRAIN_BLOCK`` where the blocks
    counted would not fit the kernel's fast memory: not at the cell's
    shape (six blocks of 1,672 queries, 784-wide rows, 37 tiles), at the
    widest rows the kernel takes; never under a vreg's lanes; and what is
    counted at the chosen block is within the limit."""
    most = knn_search.TRAIN_BLOCK
    six = knn_search._products(jax.lax.Precision.HIGHEST)
    cell = knn_search.train_block_rows(784, 37 * 128, 1672, 6 * 1672, most)
    assert cell == most == 2048
    wide = knn_search.train_block_rows(
        *knn_search._stacked_width(1023, six), 1672, 6 * 1672, most)
    assert 1024 <= wide < most and wide % knn_search.SPLIT_LANES == 0
    assert knn_search.train_block_rows(112, 6 * 128, 24, 48, most) == most
    assert knn_search.train_block_rows(1024, 48 * 128, 10 ** 5, 10 ** 5, most) == 128


def test_where_the_fused_kernel_applies():
    """What ``nearest`` can see decides: float32 operands, ``k`` within one
    vreg of running best, rows that fit fast memory and that the chip
    holds along its lanes; and never on a backend that would interpret
    the kernel (this one)."""
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    assert knn_search.unsupported_reason(f32(10, 784), f32(99, 784), 5) is None
    assert knn_search.unsupported_reason(f32(10, 784), f32(99, 784), 128) is None
    for queries, train, k, why in [
            (f32(10, 784), f32(99, 784), 129, "k=129"),
            (jax.ShapeDtypeStruct((10, 784), jnp.float64), f32(99, 784), 5, "float64"),
            (f32(10, 784), jax.ShapeDtypeStruct((99, 784), jnp.bfloat16), 5, "bfloat16"),
            (f32(10, 2000), f32(99, 2000), 5, "dim=2000"),
            (f32(10, 256), f32(99, 256), 5, "dim=256")]:
        assert why in knn_search.unsupported_reason(queries, train, k)
    assert not knn._ranks_in_the_product(f32(10, 784), f32(99, 784), 5)
    assert knn_search.query_block_rows(10_000) == 1672      # six blocks
    assert knn_search.query_block_rows(5) == 8
    assert knn_search.query_block_rows(70, 24) == 24


@pytest.mark.parametrize("maker", [_levels, _small_integers])
def test_four_shares_merged_are_the_whole(rng, maker):
    """The cell's cut tied to the whole: each of four row shares searched
    alone (the program, one share a chip), their [queries, k] answers
    merged by (distance, global row), equal the unsharded reference."""
    rows, dim, k = 1203, 16, 5
    train, queries = maker(rng, rows, dim), maker(rng, 50, dim)
    bounds = np.linspace(0, rows, 5).astype(int)
    found = [_tiled(queries, train[lo:hi], k, 128)
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
    # this backend ran the tiled search: the count exists, and stands still
    assert second["fused_query_rows"] == first["fused_query_rows"]
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


@pytest.mark.parametrize("precision,split", [("HIGHEST", 52), ("DEFAULT", 0)])
def test_transform_counts_the_rows_the_kernel_searched(rng, monkeypatch, precision, split):
    """``KnnModel.transform`` as a TPU runs it, the kernel interpreted: the
    predictions are the reference's vote, and ``knn.fused_query_rows``
    moves with ``knn.query_rows`` (``knn.fused_search_share`` reads 1.0);
    ``knn.split_product_query_rows`` moves with them where the kernel
    made the float32 product from its own parts, and stands still at the
    one pass (``knn.split_product_share`` 1.0 and 0.0)."""
    from flinkml_tpu.kernels import _mosaic

    train, queries = _levels(rng, 430, 13), _levels(rng, 52, 13)   # shapes of
    labels = rng.integers(0, 3, 430).astype(np.float64)   # this test alone
    table, asked = Table({"features": train, "label": labels}), Table({"features": queries})
    monkeypatch.setattr(_mosaic, "interpret_mode", lambda: False)
    monkeypatch.setattr(knn, "PRODUCT_PRECISION", getattr(jax.lax.Precision, precision))
    monkeypatch.setattr(knn_search, "fused_nearest", functools.partial(
        knn_search.fused_nearest, interpret=True, query_block=16, train_block=128))
    before = metrics.group("knn").snapshot()["counters"]
    got = Knn().set_k(5).fit(table).transform(asked)[0]["prediction"]
    after = metrics.group("knn").snapshot()["counters"]
    moved = lambda name: after[name] - before.get(name, 0)
    assert moved("fused_query_rows") == moved("query_rows") == 52
    assert moved("split_product_query_rows") == split
    assert moved("train_tiles") == 0
    rows, d2 = reference.k_nearest(queries, train, 5)
    stable = ~reference.unstable(d2, 5, TOL)
    assert stable.mean() > 0.9
    # (a CPU computes the one pass exactly too: the vote holds at both)
    np.testing.assert_array_equal(got[stable], reference.vote(labels, rows, 5)[stable])


SHARES = {  # metric: (layer, the counter it divides by ``knn.query_rows``)
    "knn.fused_search_share": ("KNN search", "knn.fused_query_rows"),
    "knn.split_product_share": ("Kernels", "knn.split_product_query_rows"),
}


@pytest.mark.parametrize("name", sorted(SHARES))
def test_the_share_metrics_and_their_entries(name):
    """``knn.fused_search_share`` (PR 31) and ``knn.split_product_share``
    (PR 35) as ``BENCHMARK.json`` has them: within the file's limits of
    form, listing cells that exist, read by ``counter_ratio`` as 1.0 where
    every row went that way, 0.0 where none did, nothing where the
    program has no such count."""
    import json
    import os
    import re

    from benchmark.readers import counter_ratio

    layer, counter = SHARES[name]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    at = [m["name"] for m in bench["per_layer"]].index(name)
    entry = bench["per_layer"][at]
    assert entry == {
        "name": name, "unit": "rows/row", "better": "higher",
        "source": "program_counter", "layer": layer,
        "moves": "transform_rows_per_s", "workloads": ["knn-mnist8m.transform"]}
    assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}", entry["name"])
    assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", entry["unit"])
    for line in (entry["layer"], entry["moves"], entry["source"]):
        assert 1 <= len(line) <= 200 and line.isascii() and line.isprintable()
    cells = {w["name"] for w in bench["workloads"]}
    assert set(entry["workloads"]) <= cells
    rate = next(m for m in bench["end_to_end"] if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(rate["workloads"])
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"][:at]}
    assert [m["name"] for m in bench["per_layer"]].count(entry["name"]) == 1
    assert len(json.dumps(bench)) < 64 * 1024
    with open(os.path.join(root, "benchmark", "metrics", entry["name"] + ".json")) as f:
        how = json.load(f)
    assert how["reader"] == "counter_ratio" and set(how) == {"what", "reader", "params"}
    assert how["params"] == {"num": counter, "den": "knn.query_rows"}
    obs = lambda counters: {"counters": counters, "setup_counters": {}, "units": {"calls": 7}}
    read = lambda counters: counter_ratio.read(how["params"], obs(counters))
    assert read({counter: 70_000.0, "knn.query_rows": 70_000.0}) == 1.0
    assert read({counter: 0.0, "knn.query_rows": 70_000.0}) == 0.0
    assert read({"knn.query_rows": 70_000.0}) is None          # the parent's program


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
