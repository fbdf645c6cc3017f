"""ALS: explicit reconstruction, implicit ranking, regularization
semantics, cold start, persistence, recommendations."""

import jax.numpy as jnp
import numpy as np
import pytest

from flinkml_tpu.models import ALS, ALSModel
from flinkml_tpu.table import Table


def _low_rank_ratings(n_users=40, n_items=30, rank=4, frac=0.6, seed=0,
                      noise=0.0):
    rng = np.random.default_rng(seed)
    u = rng.normal(size=(n_users, rank)) / np.sqrt(rank)
    v = rng.normal(size=(n_items, rank)) / np.sqrt(rank)
    full = u @ v.T
    mask = rng.uniform(size=full.shape) < frac
    users, items = np.nonzero(mask)
    r = full[users, items] + noise * rng.normal(size=users.shape[0])
    return users.astype(np.int64), items.astype(np.int64), r, full


def _als(rank=6, iters=12, reg=0.01, **kw):
    als = (
        ALS().set_rank(rank).set_max_iter(iters).set_reg_param(reg)
        .set_seed(0)
    )
    for name, v in kw.items():
        getattr(als, f"set_{name}")(v)
    return als


def test_explicit_reconstructs_low_rank_matrix():
    users, items, r, full = _low_rank_ratings()
    t = Table({"user": users, "item": items, "rating": r})
    model = _als().fit(t)
    # In-sample predictions recover the observed ratings.
    (out,) = model.transform(t)
    rmse = float(np.sqrt(np.mean((out["prediction"] - r) ** 2)))
    assert rmse < 0.05, rmse
    # And generalize to the held-out entries of the low-rank matrix.
    all_u, all_i = np.meshgrid(
        np.arange(full.shape[0]), np.arange(full.shape[1]), indexing="ij"
    )
    t_all = Table({"user": all_u.ravel(), "item": all_i.ravel()})
    (pred_all,) = model.transform(t_all)
    rmse_all = float(np.sqrt(np.mean(
        (pred_all["prediction"] - full.ravel()) ** 2
    )))
    assert rmse_all < 0.15, rmse_all


def test_regularization_shrinks_factors():
    users, items, r, _ = _low_rank_ratings(seed=1)
    t = Table({"user": users, "item": items, "rating": r})
    small = _als(reg=0.001).fit(t)
    large = _als(reg=10.0).fit(t)
    assert (
        np.linalg.norm(large.user_factors)
        < 0.2 * np.linalg.norm(small.user_factors)
    )


def test_cold_start_nan_and_unseen_ids():
    users, items, r, _ = _low_rank_ratings(seed=2)
    t = Table({"user": users, "item": items, "rating": r})
    model = _als(iters=3).fit(t)
    probe = Table({"user": np.asarray([0, 9999]), "item": np.asarray([0, 0])})
    (out,) = model.transform(probe)
    assert np.isfinite(out["prediction"][0])
    assert np.isnan(out["prediction"][1])


def test_string_ids_work():
    users = np.asarray(["alice", "bob", "alice", "carol", "bob", "carol"])
    items = np.asarray(["x", "x", "y", "y", "z", "z"])
    r = np.asarray([5.0, 4.0, 1.0, 2.0, 3.0, 5.0])
    t = Table({"user": users, "item": items, "rating": r})
    model = _als(rank=2, iters=8, reg=0.1).fit(t)
    (out,) = model.transform(t)
    assert np.all(np.isfinite(out["prediction"]))
    # In-sample ordering is roughly preserved for alice: x (5) > y (1).
    pa = model.transform(
        Table({"user": np.asarray(["alice", "alice"]),
               "item": np.asarray(["x", "y"])})
    )[0]["prediction"]
    assert pa[0] > pa[1]


def test_implicit_ranks_interacted_items_higher():
    rng = np.random.default_rng(3)
    n_users, n_items = 20, 15
    # Two taste clusters: even users like even items, odd like odd.
    users, items, counts = [], [], []
    for u in range(n_users):
        liked = [i for i in range(n_items) if i % 2 == u % 2]
        for i in rng.choice(liked, size=5):
            users.append(u)
            items.append(i)
            counts.append(float(rng.integers(1, 10)))
    t = Table({
        "user": np.asarray(users), "item": np.asarray(items),
        "rating": np.asarray(counts),
    })
    model = _als(rank=4, iters=10, reg=0.1, implicit_prefs=True,
                 alpha=10.0).fit(t)
    ids, scores = model.recommend_for_all_users(5)
    # Top recommendations for user 0 (even cluster) are mostly even items.
    top0 = ids[0]
    assert (top0 % 2 == 0).mean() >= 0.8
    assert np.all(np.diff(scores[0]) <= 1e-6)  # scores sorted descending


def test_implicit_rejects_negative_ratings():
    t = Table({"user": np.asarray([0]), "item": np.asarray([0]),
               "rating": np.asarray([-1.0])})
    with pytest.raises(ValueError, match="non-negative"):
        _als(implicit_prefs=True).fit(t)


def test_save_load_and_model_data_roundtrip(tmp_path):
    users, items, r, _ = _low_rank_ratings(seed=4)
    t = Table({"user": users, "item": items, "rating": r})
    model = _als(iters=4).fit(t)
    model.save(str(tmp_path / "als"))
    loaded = ALSModel.load(str(tmp_path / "als"))
    np.testing.assert_array_equal(loaded.user_factors, model.user_factors)
    (p1,) = model.transform(t)
    (p2,) = loaded.transform(t)
    np.testing.assert_allclose(p2["prediction"], p1["prediction"])
    clone = ALSModel()
    clone.copy_params_from(model)
    clone.set_model_data(*model.get_model_data())
    (p3,) = clone.transform(t)
    np.testing.assert_allclose(p3["prediction"], p1["prediction"])


def test_chunked_path_matches_single_chunk():
    """The STREAMED fit's ``CHUNK`` (``ALS.fit(Table)`` has none since PR
    38: its chunks follow the device's free memory)."""
    users, items, r, _ = _low_rank_ratings(seed=5)
    batch = {"user": users, "item": items, "rating": r}
    big = _als(iters=3).fit(iter([Table(batch)]))
    small_chunk = _als(iters=3)
    small_chunk.CHUNK = 64  # force many chunks
    small = small_chunk.fit(iter([Table(batch)]))
    assert np.abs(small.user_factors - big.user_factors).max() > 0  # another order of sums
    np.testing.assert_allclose(
        small.user_factors, big.user_factors, rtol=2e-4, atol=2e-5
    )


def test_deterministic_given_seed():
    users, items, r, _ = _low_rank_ratings(seed=6)
    t = Table({"user": users, "item": items, "rating": r})
    m1 = _als(iters=3).fit(t)
    m2 = _als(iters=3).fit(t)
    np.testing.assert_array_equal(m1.user_factors, m2.user_factors)


def test_reg_zero_underdetermined_user_stays_finite():
    # User 0 has fewer ratings than rank: with regParam=0 its system is
    # singular; the 1e-6 lambda floor must keep everything finite.
    users = np.asarray([0, 0, 1, 1, 1, 1, 1, 1, 1, 1])
    items = np.asarray([0, 1, 0, 1, 2, 3, 4, 5, 6, 7])
    r = np.linspace(1, 5, 10)
    t = Table({"user": users, "item": items, "rating": r})
    model = _als(rank=6, iters=4, reg=0.0).fit(t)
    assert np.isfinite(model.user_factors).all()
    assert np.isfinite(model.item_factors).all()
    (out,) = model.transform(t)
    assert np.isfinite(out["prediction"]).all()


def test_cumsum_reduction_matches_segment(monkeypatch):
    """FLINKML_TPU_ALS_REDUCTION=cumsum (target-sorted COO + chunked run
    totals) must produce the same factors as the segment_sum reduction,
    explicit and implicit modes (allclose — summation order differs).
    Through ``coo_fit``, the streamed formulation over a COO in RAM:
    ``ALS.fit(Table)`` forms no per-rating outer product since PR 38 and
    reads no gate."""
    from flinkml_tpu.models.als import coo_fit

    rng = np.random.default_rng(7)
    nnz = 3000
    u = rng.integers(0, 64, size=nnz).astype(np.int32)
    i = rng.integers(0, 50, size=nnz).astype(np.int32)
    r = rng.uniform(1, 5, size=nnz).astype(np.float32)

    for implicit in (False, True):
        def fit(layout):
            monkeypatch.setenv("FLINKML_TPU_ALS_REDUCTION", layout)
            return coo_fit(u, i, r, 64, 50, rank=6, max_iter=4, reg=0.1,
                           implicit=implicit, seed=0)

        seg_u, seg_i = fit("segment")
        cum_u, cum_i = fit("cumsum")
        np.testing.assert_allclose(cum_u, seg_u, rtol=5e-4, atol=5e-5)
        np.testing.assert_allclose(cum_i, seg_i, rtol=5e-4, atol=5e-5)
        # and both are the table fit's mathematics: one half-step from the
        # same start solves the same systems
        assert np.isfinite(seg_u).all() and np.abs(seg_u).max() > 0


def test_cumsum_reduction_empty_and_tiny_tables(monkeypatch):
    """The cumsum layout must match segment on degenerate inputs: an
    empty run-table path (zero chunks) and a single-rating table."""
    from flinkml_tpu.models.als import als_run_tables, coo_fit

    empty_e, empty_c = als_run_tables(np.zeros(0, np.int32), 2, 8)
    assert empty_e.shape[0] == 0 and empty_c.shape[0] == 0

    one = (np.asarray([0], np.int32), np.asarray([0], np.int32),
           np.asarray([4.0], np.float32), 1, 1)
    monkeypatch.setenv("FLINKML_TPU_ALS_REDUCTION", "cumsum")
    cum_u, _ = coo_fit(*one, rank=3, max_iter=2, reg=0.1, seed=0)
    monkeypatch.setenv("FLINKML_TPU_ALS_REDUCTION", "segment")
    seg_u, _ = coo_fit(*one, rank=3, max_iter=2, reg=0.1, seed=0)
    np.testing.assert_allclose(cum_u, seg_u, rtol=1e-5)


def test_the_streamed_formulation_and_the_table_fit_solve_the_same_systems():
    """One iteration from the same start item factors: ``coo_fit``'s
    scatter of outer products and the table fit's blocks agree."""
    from flinkml_tpu.models import _als_blocked
    from flinkml_tpu.models.als import _half_step, _pad_coo
    from flinkml_tpu.parallel import DeviceMesh

    users, items, r, _ = _low_rank_ratings(seed=8)
    model = _als(iters=1).fit(Table({"user": users, "item": items, "rating": r}))
    user_ids, u = np.unique(users, return_inverse=True)
    item_ids, i = np.unique(items, return_inverse=True)
    mesh = DeviceMesh()
    chunk = 64
    start = jnp.asarray(_als_blocked.start_factors(0, item_ids.size, 6))
    by_user = _pad_coo(u.astype(np.int32), i.astype(np.int32),
                       r.astype(np.float32), user_ids.size, mesh.axis_size() * chunk)
    user_f = _half_step(mesh, *by_user, start, user_ids.size, 0.01, False, 1.0, chunk)
    np.testing.assert_allclose(np.asarray(user_f), model.factors()[0],
                               rtol=2e-3, atol=2e-4)
