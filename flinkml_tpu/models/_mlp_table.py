"""The perceptrons' fit of a ``Table``: Adam's whole run one device
program over rows that stay on the mesh, every layer a matrix product
forward and two back.

``layers = [d_0, ..., d_L]``; with ``h_0`` a window's rows, ``W_l [d_{l-1},
d_l]`` and ``b_l``::

    h_l = tanh(h_{l-1} W_l + b_l)   (l < L)        z = h_{L-1} W_L + b_L

The classifier's loss is the weighted cross-entropy of ``softmax(z)``
against the class ids, the regressor's half the weighted square of ``z[:,
0] - y``. The backward pass is written out (no ``jax.grad``: a phase of
the program is then the lines under it, and a product's operands are
what this file hands it)::

    d_L = (softmax(z) - onehot(y)) w      (the regressor: (z - y) w)
    dW_l = h_{l-1}^T d_l        db_l = sum_rows d_l
    d_{l-1} = (d_l W_l^T) (1 - h_{l-1}^2)

all over the batch's weight, then ``_adam.adam_update`` on the tree of
``2 L`` arrays.

- **Products**: under a :class:`~flinkml_tpu.precision.PrecisionPolicy`
  that narrows compute (``precision="mixed"``) every product takes its
  two operands at ``policy.compute`` (bfloat16) and accumulates at
  ``policy.accum`` (``preferred_element_type`` float32), on every
  backend; activations, the loss, the gradients, the parameters and both
  moments are float32, and the step's jaxpr passes the FML6xx check
  before it is compiled. With no policy the products are float32 at
  ``Precision.HIGHEST``.
- **Batches**: step ``t`` reads window ``t mod ceil(rows / batch)`` of the
  rows in the order ``default_rng(seed).permutation(rows)``
  (``_linear_sgd._window``: the last window is clamped to end at the
  last row), not ``_adam``'s draw with replacement (a gather of whole
  rows every step; the streamed fit keeps it). Over ``p`` devices a window
  is dealt, device ``d`` the ``d``-th ``batch / p`` rows of it
  (:func:`dealt_order`), so where the windows are whole the steps read
  the same rows whatever the mesh.
- **Residency**: the placed rows, labels and weights are kept WITH the
  ``Table`` (:meth:`Table.device_resident`), float32 rows cast in the
  gather (:meth:`DeviceMesh.stage_rows`: no float64 copy, no copy of the
  column at all); a second fit of the table, seed and mesh uploads nothing
  of it, the start parameters are made on the device, and the fit is
  bit-equal at an equal rate.
- **One program**, ``mlp_fit``: the rate and ``tol`` are runtime operands,
  so a sweep over the rate compiles once; ``max_iter`` is the length of
  the loss curve it returns and is static.

Spans, counters and phases: ``docs/development/observability.md``
(``mlp.*``; the group ``mlp``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from flinkml_tpu.models._adam import adam_update
from flinkml_tpu.models._data import fit_columns
from flinkml_tpu.models._linear_sgd import _placed, _window, align_local_bs
from flinkml_tpu.parallel import DeviceMesh
from flinkml_tpu.utils.metrics import metrics
from flinkml_tpu.utils.profiling import named_program, phase, span

#: The step's phases (``profiling.phase``).
PHASES = ("mlp.forward", "mlp.backward", "mlp.adam")


class _Placed(NamedTuple):
    """A table's rows as the mesh holds them, in the dealt seeded order."""

    x: jax.Array        # [p * n_local, d_0] float32
    y: jax.Array        # [p * n_local] int32 class ids (the regressor: float32)
    w: jax.Array        # [p * n_local] float32, 1 at a row and 0 at the padding
    rows: int


def dealt_order(rows: int, p: int, local_bs: int, seed: int) -> np.ndarray:
    """The row at every place of the mesh's ``p`` shards of ``ceil(rows /
    p)`` places each, shard after shard (what :meth:`DeviceMesh.stage_rows`
    takes as an order; the places past ``rows``, the last shard's end, are
    its zero rows): window ``k`` of ``order = default_rng(seed).
    permutation(rows)``, ``p * local_bs`` rows of it, lies in every
    shard's ``k``-th ``local_bs`` places, ``local_bs`` rows a shard in
    turn; the rows left after the last whole window follow, shard after
    shard. One device holds ``order`` itself. Where the padding reaches
    into the last shard's whole windows the rows after it move up, so
    every row is placed once whatever the sizes."""
    order = np.random.default_rng(seed).permutation(rows)
    if p == 1:
        return order
    n_local = -(-rows // p)
    shard, place = np.divmod(np.arange(p * n_local), n_local)
    whole = n_local // local_bs * local_bs
    window, within = np.divmod(place, local_bs)
    seat = np.where(
        place < whole, (window * p + shard) * local_bs + within,
        p * whole + shard * (n_local - whole) + (place - whole))[:rows]
    taken = np.zeros(p * n_local, bool)
    taken[seat] = True
    return order[(np.cumsum(taken) - 1)[seat]]


def _place_table(x, labels, label_dtype, mesh: DeviceMesh, seed: int,
                 local_bs: int, make_room) -> _Placed:
    """Every row on the mesh, once: features as float32, labels as
    ``label_dtype``, through :meth:`DeviceMesh.stage_rows` in
    :func:`dealt_order` (its spans ``hostdata.*`` inside the caller's
    ``mlp.place``), padded with zero rows of weight 0 to the mesh;
    ``make_room`` is the table's that will keep them."""
    rows = x.shape[0]
    make_room(rows * (4 * int(np.prod(x.shape[1:])) + 8),
              mesh.mesh.devices.flat)
    with span("hostdata.shuffle"), span("hostdata.permute"):
        order = dealt_order(rows, mesh.axis_size(), local_bs, seed)
    xd, yd = _placed(mesh.stage_rows(
        [(x, order, np.float32), (labels, order, label_dtype)]))
    return _Placed(xd, yd, mesh.shard_ones(rows, np.float32), rows)


def product_of(policy):
    """``dot(a, b, contract)``: the product of ``a`` and ``b`` over the
    axes ``contract = (axis of a, axis of b)``, as the module docstring
    states it for ``policy``."""
    if policy is not None and policy.mixed:
        cast = lambda a: a.astype(policy.compute_dtype)
        kwargs = {"preferred_element_type": jnp.dtype(policy.accum_dtype)}
    else:
        cast = lambda a: a
        kwargs = {"precision": jax.lax.Precision.HIGHEST}

    def dot(a, b, contract=(1, 0)):
        return jax.lax.dot_general(
            cast(a), cast(b), (((contract[0],), (contract[1],)), ((), ())),
            **kwargs)

    return dot


def loss_and_gradients(params, xb, yb, wb, classify: bool, dot):
    """One window's weighted loss a ROW and the gradients' SUMS over its
    rows, ``(losses [rows], (dW_1, db_1, ..., dW_L, db_L))`` for ``params =
    (W_1, b_1, ..., W_L, b_L)``, by the module docstring's equations: what
    :func:`make_step` calls, under the phases ``mlp.forward`` and
    ``mlp.backward``."""
    depth = len(params) // 2
    with phase("mlp.forward"):
        hs = [xb]
        for l in range(depth - 1):
            hs.append(jnp.tanh(dot(hs[-1], params[2 * l]) + params[2 * l + 1]))
        z = dot(hs[-1], params[-2]) + params[-1]
        if classify:
            logp = jax.nn.log_softmax(z)
            hot = yb[:, None] == jnp.arange(z.shape[1], dtype=yb.dtype)[None, :]
            losses = -jnp.sum(jnp.where(hot, logp, 0.0), axis=1) * wb
        else:
            err = z[:, 0] - yb
            losses = 0.5 * err * err * wb
    with phase("mlp.backward"):
        if classify:
            delta = (jnp.exp(logp) - hot.astype(z.dtype)) * wb[:, None]
        else:
            delta = (err * wb)[:, None]
        grads = []
        for l in reversed(range(depth)):
            grads += [jnp.sum(delta, axis=0), dot(hs[l], delta, (0, 0))]
            if l:
                delta = dot(delta, params[2 * l], (1, 1)) * (1.0 - hs[l] * hs[l])
    return losses, tuple(reversed(grads))


def _windows(x, y, w, t, local_bs: int):
    """Window ``t`` of a shard's rows, labels and weights."""
    return tuple(_window(a, t, local_bs) for a in (x, y, w))


def make_step(classify: bool, local_bs: int, axis: str, dot):
    """ONE Adam step on a device's shard: ``step(params, m, v, t, x, y, w,
    lr) -> (params, m, v, loss)``, ``t`` the global 0-based step (the
    window and Adam's bias correction)."""

    def step(params, m, v, t, x, y, w, lr):
        with phase("mlp.forward"):
            xb, yb, wb = _windows(x, y, w, t, local_bs)
        losses, grads = loss_and_gradients(params, xb, yb, wb, classify, dot)
        with phase("mlp.adam"):
            # joined over the mesh and divided by the batch's weight
            total_w = jnp.maximum(jax.lax.psum(jnp.sum(wb), axis), 1e-12)
            loss = jax.lax.psum(jnp.sum(losses), axis) / total_w
            grads = jax.tree.map(lambda g: jax.lax.psum(g, axis) / total_w, grads)
            params, m, v = adam_update(params, m, v, grads, t, lr)
        return params, m, v, loss

    return step


def _check_policy(policy, step, layers, local_bs: int, label_dtype, p: int,
                  axis: str) -> None:
    """The FML6xx gate, before any compile: the step traced over the
    shapes it will run at, the parameters and both moments its state."""
    from flinkml_tpu.analysis.precision import validate_precision

    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    tree = tuple(s for a, b in zip(layers, layers[1:]) for s in (f32(a, b), f32(b)))
    validate_precision(
        step, tree, tree, tree, jax.ShapeDtypeStruct((), jnp.int32),
        f32(local_bs, layers[0]), jax.ShapeDtypeStruct((local_bs,), label_dtype),
        f32(local_bs), f32(),
        policy=policy, param_argnums=(0, 1, 2), program="mlp_fit",
        axis_env=[(axis, p)])


@functools.lru_cache(maxsize=32)
def _trainer(mesh, layers: Tuple[int, ...], classify: bool, local_bs: int,
             axis: str, max_iter: int, policy):
    """Adam's whole run as one program, ``mlp_fit``: ``(params, x, y, w, lr,
    tol) -> (params, steps, losses [max_iter])``. It stops after ``max_iter``
    steps, or when two successive losses lie within ``tol`` of each other
    as ``_adam``'s loop does; at ``tol`` 0 it runs ``max_iter`` steps
    exactly unless the loss is NaN. ``losses`` holds every step's loss
    and NaN past the last."""
    step = make_step(classify, local_bs, axis, product_of(policy))
    if policy is not None:
        _check_policy(policy, step, layers, local_bs,
                      jnp.int32 if classify else jnp.float32,
                      mesh.shape[axis], axis)

    def per_device(params, x, y, w, lr, tol):
        zeros = jax.tree.map(jnp.zeros_like, params)

        def cond(state):
            t, _, _, _, prev, cur, _ = state
            moving = jnp.where(tol > 0, jnp.abs(prev - cur) > tol,
                               ~jnp.isnan(cur))
            return (t < max_iter) & moving

        def body(state):
            t, params, m, v, _, last, losses = state
            params, m, v, loss = step(params, m, v, t, x, y, w, lr)
            return t + 1, params, m, v, last, loss, losses.at[t].set(loss)

        inf = jnp.asarray(jnp.inf, jnp.float32)
        t, params, _, _, _, _, losses = jax.lax.while_loop(
            cond, body, (jnp.asarray(0, jnp.int32), params, zeros, zeros, inf,
                         -inf, jnp.full((max_iter,), jnp.nan, jnp.float32)))
        return params, t, losses

    return jax.jit(jax.shard_map(
        named_program("mlp_fit", per_device, phases=PHASES), mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(axis), P(), P()),
        out_specs=(P(), P(), P()),
    ))


def init_params(layers, key) -> Tuple:
    """``(W_1, b_1, ..., W_L, b_L)`` float32 from a key: He-scaled normal
    weights (``sqrt(2 / d_{l-1})``), zero biases. The streamed fit's
    start too."""
    params = []
    for d_in, d_out in zip(layers, layers[1:]):
        key, sub = jax.random.split(key)
        params += [jax.random.normal(sub, (d_in, d_out), jnp.float32)
                   * jnp.sqrt(2.0 / d_in),
                   jnp.zeros(d_out, jnp.float32)]
    return tuple(params)


@functools.lru_cache(maxsize=32)
def _start_program(layers: Tuple[int, ...]):
    """:func:`init_params` of ``layers`` as one program."""

    def start(key):
        return init_params(layers, key)

    return jax.jit(named_program("mlp_start", start))


def start_params(layers, seed: int, mesh: DeviceMesh) -> Tuple:
    """A fit's start, made on the device and replicated over ``mesh``: a
    function of ``(layers, seed)``."""
    return mesh.replicate(
        _start_program(tuple(int(d) for d in layers))(jax.random.PRNGKey(seed)))


def _placed_table(est, table, classify: bool):
    """``(placed, mesh, layers, a device's rows a step)``: the table's rows
    on the estimator's mesh, found with the table or placed and kept (the
    span ``mlp.place``), its labels checked from the kept facts."""
    layers = est._check_layers()
    features_col, label_col = est.get(est.FEATURES_COL), est.get(est.LABEL_COL)
    x, labels, _ = fit_columns(table, features_col, label_col)
    if x.shape[0] == 0:
        raise ValueError("training table is empty")
    if x.shape[1] != layers[0]:
        raise ValueError(f"layers[0]={layers[0]} != feature dim {x.shape[1]}")
    est._check_labels(labels, layers)
    mesh = est.mesh or DeviceMesh()
    seed = est.get_seed()
    p = mesh.axis_size()
    local_bs = align_local_bs(est.get(est.GLOBAL_BATCH_SIZE), p, -(-x.shape[0] // p))
    label_dtype = np.int32 if classify else np.float32
    sent = 0.0

    def place(make_room):
        nonlocal sent
        placed = _place_table(x, labels.values, label_dtype, mesh, seed,
                              local_bs, make_room)
        sent = float(placed.x.nbytes + placed.y.nbytes)
        return placed

    with span("mlp.place"):
        # One device holds the seeded order itself, whatever the batch.
        placed = table.device_resident(
            ("mlp_rows_on_mesh", features_col, label_col, mesh.mesh,
             np.dtype(label_dtype).name, seed, local_bs if p > 1 else None),
            place)
    # Counted at every fit, 0 at a hit.
    metrics.group("mlp").counter("table_h2d_bytes", sent)
    return placed, mesh, tuple(int(d) for d in layers), local_bs


def fit_table(est, table, classify: bool):
    """``MLPClassifier.fit`` / ``MLPRegressor.fit`` of a ``Table``:
    ``(params as float32 host arrays, losses [steps])``. The caller's
    span ``fit`` holds all of it."""
    placed, mesh, layers, local_bs = _placed_table(est, table, classify)
    policy = est.precision
    trainer = _trainer(mesh.mesh, layers, classify, local_bs,
                       DeviceMesh.DATA_AXIS, int(est.get(est.MAX_ITER)),
                       policy)
    with span("mlp.dispatch"):
        out = trainer(
            start_params(layers, est.get_seed(), mesh), placed.x, placed.y,
            placed.w, np.float32(est.get(est.LEARNING_RATE)),
            np.float32(est.get(est.TOL)))
    with span("mlp.readback"):
        # The first read waits for the loop: the device's time lies here.
        steps = int(out[1])
        params, losses = jax.device_get((out[0], out[2]))
        losses = losses[:steps]
    group = metrics.group("mlp")
    group.counter("fits")
    group.counter("steps", float(steps))
    group.counter("rows", float(placed.rows))
    group.counter("policy_steps", float(steps) if policy is not None else 0.0)
    return params, losses
