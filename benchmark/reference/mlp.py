"""A multilayer perceptron trained by Adam over windows of a seeded
order: the benchmark's own copy, ``jax.numpy`` in float32 with every
product at ``highest``. It imports nothing of the program or of
``tests/``.

**This reference runs on the chip** (the first of the benchmark's that is
not NumPy on the host): a fit of the cell is 124 steps of 1.11 TFLOP,
which a host does not finish inside a run. It is plain all the same: a
Python loop over the steps, one function a step, no ``while_loop``, no
kernel, nothing kept between calls but what is passed; a window's rows
are gathered on the host and handed over, and the batch is taken in
``block`` rows at a time where a caller says so (the sums over the batch
are then sums of the blocks' sums). On a TPU a float32 product is one
bfloat16 pass unless told otherwise: ``jax.default_matmul_precision
("highest")`` is set around everything here.

The net is Ciresan, Meier, Gambardella, Schmidhuber, *Deep, Big, Simple
Neural Nets for Handwritten Digit Recognition* (Neural Computation
22(12), 2010; arXiv:1003.0358): layers ``d_0 - d_1 - ... - d_L`` fully
connected, ``W_l [d_{l-1}, d_l]`` and ``b_l``, with ``h_0`` the rows::

    a_l = h_{l-1} W_l + b_l        h_l = tanh(a_l)  (l < L)
    p   = softmax(a_L)
    loss = - sum_r c_r log p[r, y_r] / C,     C = sum_r c_r

and the backward pass written out, ``c`` the rows' weights::

    d_L = (p - onehot(y)) c
    d_l = (d_{l+1} W_{l+1}^T) (1 - h_l ** 2)
    grad W_l = h_{l-1}^T d_l / C       grad b_l = sum_r d_l[r] / C

Each departure from the source is a comment at its line below (the
configuration's ``assumed`` lists them too): Adam over batches for online
back-propagation, plain tanh for the scaled one, a He-scaled normal start
(:func:`start`: the reference draws it itself), no deformation pass.

Step ``t`` reads window ``t mod ceil(rows / batch)`` of the seeded order,
pulled back so that a last, short window still holds ``batch`` rows
(:func:`step_rows`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8


def seeded_order(seed: int, rows: int) -> np.ndarray:
    """The row order a seed fixes."""
    return np.random.default_rng(int(seed)).permutation(rows)


def step_rows(order: np.ndarray, batch: int, t: int) -> np.ndarray:
    """The rows step ``t`` reads: ``batch`` positions of ``order``."""
    n = order.shape[0]
    batch = min(batch, n)
    windows = -(-n // batch)
    start = min((t % windows) * batch, n - batch)
    # Source: one image a step, in a new random order every epoch. Here a
    # step is a batch of thousands (a chip's step), the order one seeded
    # permutation, and an epoch its windows in turn.
    return order[start:start + batch]


def start(layers, seed: int):
    """``(W_1, b_1, ..., W_L, b_L)`` float32, a function of ``(layers,
    seed)``: ``W_l`` standard normal times ``sqrt(2 / d_{l-1})`` (He, Zhang,
    Ren, Sun, ICCV 2015) from the ``l``-th key split off
    ``jax.random.PRNGKey(seed)`` in turn, so that no two layers share a
    draw; ``b_l`` zero."""
    # Source: every weight uniform in [-0.05, 0.05]. The estimator states a
    # He-scaled normal start with zero biases and takes no start from its
    # caller, so the reference draws the stated one and the benchmark holds
    # the program's to it.
    key, params = jax.random.PRNGKey(int(seed)), []
    for d_in, d_out in zip(layers, layers[1:]):
        key, sub = jax.random.split(key)
        params += [np.asarray(jax.random.normal(sub, (d_in, d_out), jnp.float32))
                   * np.float32(np.sqrt(2.0 / d_in)),
                   np.zeros(d_out, np.float32)]
    return tuple(params)


def _highest(fn):
    """``fn`` with every float32 product exact to float32."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return wrapped


def forward(params, x):
    """``([h_0, ..., h_{L-1}], a_L)`` for ``params = (W_1, b_1, ..., W_L,
    b_L)`` and rows ``x``."""
    hs = [x]
    for l in range(len(params) // 2 - 1):
        # Source: 1.7159 tanh(2 a / 3), the scaled hyperbolic tangent. The
        # estimator's hidden unit is plain tanh (Spark ML's perceptron's
        # family uses a sigmoid; the program documents tanh).
        hs.append(jnp.tanh(hs[-1] @ params[2 * l] + params[2 * l + 1]))
    return hs, hs[-1] @ params[-2] + params[-1]


def _log_softmax(a):
    shifted = a - jnp.max(a, axis=1, keepdims=True)
    return shifted - jnp.log(jnp.sum(jnp.exp(shifted), axis=1, keepdims=True))


@jax.jit
@_highest
def row_losses(params, x, y):
    """``- log p[r, y_r]`` a row: the forward pass alone."""
    logp = _log_softmax(forward(params, x)[1])
    return -jnp.sum(jax.nn.one_hot(y, logp.shape[1], dtype=logp.dtype) * logp, axis=1)


@jax.jit
@_highest
def _block_sums(params, x, y, c):
    """The weighted loss SUM and the gradients' SUMS over the rows of one
    block, by the module docstring's equations."""
    hs, a = forward(params, x)
    logp = _log_softmax(a)
    hot = jax.nn.one_hot(y, a.shape[1], dtype=a.dtype)
    loss = -jnp.sum(jnp.sum(hot * logp, axis=1) * c)
    d = (jnp.exp(logp) - hot) * c[:, None]
    grads = [None] * len(params)
    for l in reversed(range(len(params) // 2)):
        grads[2 * l] = hs[l].T @ d
        grads[2 * l + 1] = jnp.sum(d, axis=0)
        if l:
            d = (d @ params[2 * l].T) * (1.0 - hs[l] ** 2)
    return loss, tuple(grads)


def loss_and_gradients(params, x, y, c=None, block: int = 0):
    """``(loss, grads)`` of the rows ``x`` with class ids ``y`` and weights
    ``c`` (None: one a row) at ``params``, both over the rows' weight:
    device arrays. ``block`` > 0 takes the rows that many at a time."""
    x = np.asarray(x, np.float32)
    c = np.ones(x.shape[0], np.float32) if c is None else np.asarray(c, np.float32)
    y = np.asarray(y, np.int32)
    block = block or x.shape[0]
    params = tuple(jnp.asarray(p, jnp.float32) for p in params)
    loss, grads = None, None
    for lo in range(0, x.shape[0], block):
        part = _block_sums(params, x[lo:lo + block], y[lo:lo + block],
                           c[lo:lo + block])
        if loss is None:
            loss, grads = part
        else:
            loss = loss + part[0]
            grads = tuple(g + h for g, h in zip(grads, part[1]))
    total = float(c.sum())
    return loss / total, tuple(g / total for g in grads)


@jax.jit
def _adam(params, m, v, grads, t, lr):
    """Adam as published (Kingma and Ba, ICLR 2015, algorithm 1): rates
    0.9 and 0.999, epsilon 1e-8 outside the root, both moments
    bias-corrected; ``t`` the 1-based step."""
    # Source: plain online gradient descent at a rate that decays from
    # 1e-3 by a factor a epoch. Adam is the estimator's optimizer.
    m = tuple(B1 * a + (1 - B1) * g for a, g in zip(m, grads))
    v = tuple(B2 * a + (1 - B2) * g * g for a, g in zip(v, grads))
    params = tuple(
        p - lr * (a / (1 - B1 ** t)) / (jnp.sqrt(b / (1 - B2 ** t)) + EPS)
        for p, a, b in zip(params, m, v))
    return params, m, v


def fit(x, y, order, params0, lr: float, steps: int, batch: int, block: int = 0):
    """``steps`` steps of Adam at rate ``lr`` from ``params0`` over the
    windows of ``order``: ``(params, losses [steps])``, host float32.
    ``x`` and ``y`` are the host's table, whole; a step gathers its window.
    """
    params = tuple(jnp.asarray(p, jnp.float32) for p in params0)
    m = tuple(jnp.zeros_like(p) for p in params)
    v = tuple(jnp.zeros_like(p) for p in params)
    losses = []
    for t in range(steps):
        rows = step_rows(order, batch, t)
        # Source: every epoch's digits are deformed anew (elastic, rotation,
        # scaling) before the pass. Here the table's rows ARE the deformed
        # digits (mnist8m is that pass run 134 times and stored).
        loss, grads = loss_and_gradients(params, x[rows], y[rows], None, block)
        params, m, v = _adam(params, m, v, grads, jnp.float32(t + 1),
                             jnp.float32(lr))
        losses.append(loss)
    return (tuple(np.asarray(p) for p in params),
            np.asarray(jnp.stack(losses)) if losses else np.zeros(0, np.float32))


def relative_gaps(got, want) -> list:
    """``|got - want|_F / |want|_F`` a pair of arrays."""
    return [float(np.linalg.norm(np.asarray(g, np.float64) - np.asarray(w, np.float64))
                  / max(np.linalg.norm(np.asarray(w, np.float64)), 1e-300))
            for g, w in zip(got, want)]
