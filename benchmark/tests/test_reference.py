"""The NumPy references against closed forms."""

import numpy as np

from benchmark import datagen
from benchmark.reference import chain, linear


def _identity_model(d):
    one, zero = np.ones(d), np.zeros(d)
    return {"mean": zero, "std": one, "dataMin": zero, "dataMax": one,
            "maxAbs": one, "median": zero, "range": one,
            "coefficient": np.arange(1.0, d + 1.0)}


def test_chain_with_identity_scalers_is_the_dot_product():
    x = np.array([[1.0, 2.0, 3.0], [-1.0, 0.0, 0.0]])
    dot, pred, raw = chain.chain(_identity_model(3), x)
    np.testing.assert_allclose(dot, [14.0, -1.0])
    np.testing.assert_array_equal(pred, [1.0, 0.0])
    np.testing.assert_allclose(raw[:, 1], 1.0 / (1.0 + np.exp([-14.0, 1.0])))
    np.testing.assert_allclose(raw.sum(axis=1), 1.0)


def test_chain_each_stage_by_hand():
    md = {"mean": np.array([1.0]), "std": np.array([2.0]),
          "dataMin": np.array([-1.0]), "dataMax": np.array([3.0]),
          "maxAbs": np.array([0.5]), "median": np.array([9.0]),
          "range": np.array([0.25]), "coefficient": np.array([2.0])}
    # x = 5: (5-1)/2 = 2; (2+1)/4 = 0.75; /0.5 = 1.5; /0.25 = 6 (no
    # centring: the median is not used); dot = 12.
    dot, _, _ = chain.chain(md, np.array([[5.0]]))
    assert dot[0] == 12.0


def test_chain_constant_feature_conventions():
    md = _identity_model(2)
    md["std"] = np.array([0.0, 1.0])        # divide by 1
    md["dataMax"] = np.array([0.0, 1.0])    # span 0 -> 0.5
    md["coefficient"] = np.array([1.0, 0.0])
    dot, _, _ = chain.chain(md, np.array([[7.0, 0.0]]))
    assert dot[0] == 0.5


def test_compare_counts_mismatches_only_away_from_the_boundary():
    md = _identity_model(1)
    x = np.array([[2.0], [-2.0], [1e-5]])
    wrong = np.array([0.0, 0.0, 0.0])       # first is wrong, third is near 0
    got = chain.compare(md, x, wrong)
    assert got["pred_mismatch_away"] == 1 and got["raw_max_abs_err"] is None
    _, _, raw = chain.chain(md, x)
    got = chain.compare(md, x, np.array([1.0, 0.0, 1.0]), raw + 1e-3)
    assert got["pred_mismatch_away"] == 0
    assert abs(got["raw_max_abs_err"] - 1e-3) < 1e-12


def test_first_gradient_step_closed_form():
    # From c = 0: sigmoid(0) = 1/2, so c1 = rate / n * sum_i s_i x_i / 2.
    rng = np.random.default_rng(0)
    x = rng.standard_normal((64, 5))
    y = (rng.random(64) > 0.5).astype(np.float64)
    c1 = linear.full_batch_gd(x, y, 1, 0.5)
    want = 0.5 / 64 * ((2 * y - 1)[:, None] * x).sum(axis=0) / 2
    np.testing.assert_allclose(c1, want, rtol=1e-13)


def test_gradient_descent_lowers_the_loss_and_is_order_independent():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((512, 8))
    y = (x @ rng.standard_normal(8) > 0).astype(np.float64)
    c = linear.full_batch_gd(x, y, 50, 0.5)
    assert linear.log_loss(x @ c, y) < 0.5 * np.log(2.0)
    perm = rng.permutation(512)
    np.testing.assert_allclose(linear.full_batch_gd(x[perm], y[perm], 50, 0.5), c,
                               rtol=1e-10, atol=1e-14)


def test_minibatch_sgd_windows_rotate_in_the_given_order():
    # Two windows of two rows, three steps: windows 0, 1, 0 of the order.
    x = np.array([[1.0, 0.0], [0.0, 1.0], [2.0, 0.0], [0.0, 3.0]])
    y = np.array([1.0, 0.0, 0.0, 1.0])
    order = np.array([2, 0, 3, 1])
    c = np.zeros(2)
    for rows in ([2, 0], [3, 1], [2, 0]):
        s = 2 * y[rows] - 1
        mult = -s / (1.0 + np.exp(s * (x[rows] @ c)))
        c = c - 0.5 / 2 * x[rows].T @ mult
    got = linear.minibatch_sgd(x, y, 3, 0.5, 2, order, threads=2)
    np.testing.assert_allclose(got, c, rtol=1e-13)
    # a batch of every row is the full-batch run, whatever the order
    np.testing.assert_allclose(linear.minibatch_sgd(x, y, 5, 0.5, 4, order),
                               linear.full_batch_gd(x, y, 5, 0.5), rtol=1e-13)


def test_minibatch_sgd_pulls_a_short_last_window_back():
    # 5 rows, batch 2: windows start at 0, 2 and (4 pulled back to) 3.
    rng = np.random.default_rng(3)
    x, y = rng.standard_normal((5, 3)), np.array([1.0, 0.0, 1.0, 1.0, 0.0])
    order = np.arange(5)
    got = linear.minibatch_sgd(x, y, 3, 0.5, 2, order, threads=1)
    c = np.zeros(3)
    for rows in ([0, 1], [2, 3], [3, 4]):
        s = 2 * y[rows] - 1
        c = c - 0.5 / 2 * x[rows].T @ (-s / (1.0 + np.exp(s * (x[rows] @ c))))
    np.testing.assert_allclose(got, c, rtol=1e-13)


def test_seeded_order_is_numpys_permutation_of_the_seed():
    np.testing.assert_array_equal(linear.seeded_order(7, 100),
                                  np.random.default_rng(7).permutation(100))


def test_log_loss_at_zero_margins_is_ln2():
    assert abs(linear.log_loss(np.zeros(9), np.ones(9)) - np.log(2.0)) < 1e-15


def test_bfloat16_rounding_by_hand():
    # 1 + 2**-8 is a tie between 1 and 1 + 2**-7: to even, down to 1;
    # 1 + 3 * 2**-8 ties up to 1 + 2**-6; pi keeps 8 significant bits.
    a = np.array([1.0 + 2.0 ** -8, 1.0 + 3 * 2.0 ** -8, np.pi, -0.0, 65504.0],
                 np.float32)
    got = linear.to_bfloat16(a)
    np.testing.assert_array_equal(
        got, np.array([1.0, 1.0 + 2.0 ** -6, 3.140625, -0.0, 65536.0], np.float32))


def test_the_same_seed_gives_the_same_bytes_and_a_large_seed_works():
    big = 2 ** 31 + 12345
    a = datagen.normal_matrix(big, datagen.TAG_FEATURES, 70_000, 3)
    b = datagen.normal_matrix(big, datagen.TAG_FEATURES, 70_000, 3)
    assert a.dtype == np.float32 and np.array_equal(a, b)
    c = datagen.normal_matrix(big + 1, datagen.TAG_FEATURES, 70_000, 3)
    assert not np.array_equal(a, c)
    md = datagen.chain_model_data(big, 123)
    assert abs(md["coefficient"].sum()) < 1e-12
    assert np.array_equal(datagen.sample_rows(big, 1000, 10, 3),
                          datagen.sample_rows(big, 1000, 10, 3))
