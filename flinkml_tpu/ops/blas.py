"""BLAS facade — the numeric kernel layer.

Parity: ``flink-ml-core/.../ml/linalg/BLAS.java:26-91`` exposes
``asum/axpy/dot/norm2/scal/gemv`` over ``double[]`` via pure-Java netlib;
that facade is the *entire* kernel layer of the reference. Here every op is
a jax.numpy expression: XLA fuses elementwise chains and maps matmuls onto
the MXU, and the same functions trace cleanly inside ``jit``/``grad``/
``vmap``/``shard_map``.

Batched variants (``gemm``, ``batch_dot``, ``squared_distances``) are the
TPU-first additions: the reference calls gemv per row (e.g.
``KnnModel.java:72-197``); on TPU the batch dimension belongs in the kernel.

Functions accept jax or numpy arrays and return jax arrays. Precision policy:
computations run in the input dtype; algorithms choose float32 (TPU-native)
and tests may use float64 on CPU (x64 enabled in conftest). The distance
expansion states its product's precision (:data:`DISTANCE_PRECISION`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from flinkml_tpu.utils.profiling import named_program

Array = jax.Array


def asum(x) -> Array:
    """Sum of absolute values. Parity: BLAS.java asum."""
    return jnp.sum(jnp.abs(x))


def axpy(a, x, y) -> Array:
    """a*x + y (functional: returns the result instead of mutating y).

    Parity: BLAS.java axpy — the reference mutates ``y`` in place; under XLA
    arrays are immutable and the fused result is returned.
    """
    return a * x + y


def dot(x, y) -> Array:
    """Vector dot product. Parity: BLAS.java dot."""
    return jnp.dot(x, y)


def norm2(x) -> Array:
    """Euclidean norm. Parity: BLAS.java norm2."""
    return jnp.sqrt(jnp.sum(x * x))


def scal(a, x) -> Array:
    """a*x (functional). Parity: BLAS.java scal."""
    return a * x


def gemv(alpha, matrix, x, beta=0.0, y=None, trans: bool = False) -> Array:
    """alpha * op(A) @ x + beta * y. Parity: BLAS.java gemv."""
    a = matrix.T if trans else matrix
    out = alpha * (a @ x)
    if y is not None:
        out = out + beta * y
    return out


# -- batched TPU-first additions -------------------------------------------

def gemm(a, b) -> Array:
    """Plain matmul (MXU path); inputs [m,k] @ [k,n]."""
    return a @ b


def batch_dot(xs, y) -> Array:
    """Row-wise dot of a batch [n, d] against a vector [d] -> [n]."""
    return xs @ y


@jax.jit
@functools.partial(named_program, "rows_sq")
def squared_norms(xs) -> Array:
    """Each row's squared L2 norm: [n, d] -> [n]. One program a shape,
    for a caller that keeps a placed table's norms beside it (KNN's model
    data, a KMeans table) and hands them to :func:`squared_distances`."""
    return jnp.sum(xs * xs, axis=-1)


#: The precision of the one product in :func:`squared_distances`: float32
#: accuracy. A TPU's default is ONE bfloat16 pass, which rounds both
#: operands to 8 bits of mantissa: distances off by a part in a few
#: hundred, enough to move thousands of a large table's rows to another
#: centroid or a query's neighbours to other rows (PERF.md §2).
DISTANCE_PRECISION = jax.lax.Precision.HIGHEST


def squared_distances(xs, ys, *, precision=DISTANCE_PRECISION,
                      xs_sq=None, ys_sq=None) -> Array:
    """Pairwise squared L2 distances: [n, d] x [m, d] -> [n, m].

    THE expansion (‖x‖² - 2x·y + ‖y‖²) of the program: the dominant cost
    is one [n,d]@[d,m] matmul on the MXU instead of an O(n·m·d)
    elementwise broadcast that would blow HBM. KMeans (both trainers and
    the model), KNN's tiled search and every other caller share it, so
    they share its ``precision``: the product's, stated, float32 accuracy
    unless a caller (a builder's control) passes another. On a CPU every
    precision is the same arithmetic.

    ``xs_sq`` ([n]) and ``ys_sq`` ([m]) are the rows' squared norms where
    the caller has them already (a table's, computed once and not once a
    round); a caller that holds ``ys`` as columns passes ``ys_t.T``, and
    the two transposes cancel before the compiler lays anything out.
    """
    xs = jnp.asarray(xs)
    ys = jnp.asarray(ys)
    x2 = (jnp.sum(xs * xs, axis=-1) if xs_sq is None else xs_sq)[:, None]
    y2 = (jnp.sum(ys * ys, axis=-1) if ys_sq is None else ys_sq)[None, :]
    d2 = x2 - 2.0 * jnp.matmul(xs, ys.T, precision=precision) + y2
    return jnp.maximum(d2, 0.0)
