"""The one sparse step (``_linear_sgd.make_sparse_step_bucketed``: the
plain ELL matvec forward, one fused ``segment_sum`` gradient) against
NumPy float64, through ``train_linear_model_sparse_csr`` on the
eight-device mesh:

- a replay of the fit in the row order the seed fixes, for the three
  losses, rows of one width and rows in several nnz buckets, with a
  weight column and without. The replay takes its loss multipliers from
  ``tests/reference_sparse_sgd.py`` and imports nothing of the program
  but the bucket widths (a policy with tests of its own,
  ``test_sparse_scale.py``); it places the rows as the fit does: every
  bucket permuted by the one seeded generator, padded to the mesh with
  weight-0 rows, cut into one contiguous shard a device, each device
  taking its own rotating window a step. **The same replay at bfloat16
  values and coefficient is outside the tolerance**, so the comparison
  can tell the trainer's float32 from the next precision down;
- the forward margin ``ops.sparse.ell_matvec`` alone.
"""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from flinkml_tpu.models._linear_sgd import train_linear_model_sparse_csr
from flinkml_tpu.ops.sparse import choose_ell_widths, ell_matvec
from tests import reference_sparse_sgd as reference

DIM, ROWS, BATCH, STEPS, RATE, SEED = 301, 1003, 256, 30, 0.5, 11
#: Widest coefficient gap allowed between the float32 fit and the float64
#: replay: the twelve fits read 1.1e-8 to 4.6e-8 (coefficients up to
#: 0.09-0.25), the bfloat16 replays 8.5e-4 to 2.7e-3.
COEF_TOL = 1e-6


def _rows(uniform):
    """CSR rows (7 cells each, or 0 to 40 so that several buckets form),
    0/1 labels and weights, from a seed."""
    rng = np.random.default_rng(5)
    nnz = (np.full(ROWS, 7) if uniform
           else np.clip(rng.geometric(0.2, size=ROWS) - 1, 0, 40))
    indptr = np.zeros(ROWS + 1, np.int64)
    np.cumsum(nnz, out=indptr[1:])
    indices = np.concatenate(
        [np.sort(rng.choice(DIM, k, replace=False)) for k in nnz]
    ).astype(np.int32)
    values = rng.normal(size=indices.size).astype(np.float32)
    y = (rng.random(ROWS) < 0.4).astype(np.float32)
    return indptr, indices, values, y, (rng.random(ROWS) + 0.5)


def _replay(indptr, indices, values, y, w, loss, widths, devices, rounded):
    """``STEPS`` updates from zero coefficients over the rows the fit's
    devices see each step; ``rounded`` rounds the values and, after every
    step, the coefficient (bfloat16 for the control)."""
    nnz = np.diff(indptr)
    x = np.zeros((ROWS, DIM))
    x[np.repeat(np.arange(ROWS), nnz), indices] = rounded(values)
    which = np.searchsorted(np.asarray(widths), np.maximum(nnz, 1))
    rng = np.random.default_rng(SEED)
    shards = []
    for b in range(len(widths)):
        rows = np.nonzero(which == b)[0]
        if rows.size == 0:
            continue
        n_local = -(-rows.size // devices)
        placed = np.full(n_local * devices, -1)      # -1: a padding row
        placed[:rows.size] = rows[rng.permutation(rows.size)]
        local = min(max(1, math.ceil(BATCH * rows.size / (ROWS * devices))),
                    n_local)
        shards.append((placed.reshape(devices, n_local), local))
    c = np.zeros(DIM)
    for k in range(STEPS):
        batch = []
        for placed, local in shards:
            n_local = placed.shape[1]
            lo = min((k % -(-n_local // local)) * local, n_local - local)
            batch.append(placed[:, lo:lo + local].reshape(-1))
        rows = np.concatenate(batch)
        rows = rows[rows >= 0]
        m = reference._multiplier(loss, x[rows] @ c, y[rows], w[rows])
        c = rounded(c - RATE / w[rows].sum() * (x[rows].T @ m))
    return c


def _bfloat16(a):
    return np.asarray(a, np.float64).astype(jnp.bfloat16).astype(np.float64)


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unit-weights", "weight-column"])
@pytest.mark.parametrize("uniform", [True, False],
                         ids=["one-width", "several-buckets"])
@pytest.mark.parametrize("loss", ["logistic", "hinge", "squared"])
def test_sparse_fit_matches_the_float64_replay(mesh, loss, uniform, weighted):
    indptr, indices, values, y, w = _rows(uniform)
    widths = choose_ell_widths(np.diff(indptr), max_buckets=4)
    assert (len(widths) == 1) == uniform
    got = np.asarray(train_linear_model_sparse_csr(
        indptr, indices, values, DIM, y, w if weighted else None, loss=loss,
        mesh=mesh, max_iter=STEPS, learning_rate=RATE,
        global_batch_size=BATCH, reg=0.0, elastic_net=0.0, tol=0.0,
        seed=SEED), np.float64)
    replay = (indptr, indices, values, y.astype(np.float64),
              w if weighted else np.ones(ROWS), loss, widths,
              mesh.axis_size())
    want = _replay(*replay, rounded=lambda a: np.asarray(a, np.float64))
    assert np.abs(want).max() > 0.05
    assert np.abs(got - want).max() < COEF_TOL
    low = _replay(*replay, rounded=_bfloat16)
    assert np.abs(low - want).max() > 100 * COEF_TOL


@pytest.mark.parametrize("case", ["float32", "float64", "bfloat16",
                                  "zero-rows", "zero-width"])
def test_ell_matvec_matches_numpy(case):
    """``out[r] = sum_s values[r, s] * w[indices[r, s]]`` in the operands'
    dtype; a block with no rows gives ``[0]``, one with no cells zeros."""
    rng = np.random.default_rng(3)
    dtype = case if case in ("float32", "float64", "bfloat16") else "float32"
    rows, width = {"zero-rows": (0, 16), "zero-width": (35, 0)}.get(
        case, (35, 16))
    dim = 512
    ib = rng.integers(0, dim, (rows, width)).astype(np.int32)
    vb = jnp.asarray(rng.normal(size=(rows, width))).astype(dtype)
    w = jnp.asarray(rng.normal(size=dim)).astype(dtype)
    out = ell_matvec(jnp.asarray(ib), vb, w)
    assert out.shape == (rows,) and out.dtype == jnp.dtype(dtype)
    want = (np.asarray(vb, np.float64)
            * np.asarray(w, np.float64)[ib]).sum(axis=1)
    tol = {"float32": 1e-5, "float64": 1e-13, "bfloat16": 0.25}[dtype]
    np.testing.assert_allclose(np.asarray(out, np.float64), want, atol=tol)
