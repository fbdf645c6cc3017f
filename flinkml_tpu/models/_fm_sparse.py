"""The factorization machines' fit of a ``table.CsrColumn``: a
second-order FM (Rendle, ICDM 2010) trained over a sparse table that
stays on the mesh, Adam's whole run one device program.

The dense fit (``models/fm.py``) needs a ``[rows, dim]`` matrix, which a
hashed click log at ``dim`` 1e6 cannot be. Here the cells are all there
is. For a row with cells ``(i_s, x_s)``, the parameters ONE table ``P
[dim, 1 + k]`` of a column's weight and its ``k`` factors::

    S_f = sum_s x_s V[i_s, f]
    y^  = w0 + sum_s x_s w[i_s] + 1/2 sum_f (S_f**2 - sum_s x_s**2 V[i_s, f]**2)

and with ``mult = d loss / d y^`` (logistic or squared, times the row's
weight)::

    dw[i_s]    += mult * x_s
    dV[i_s, f] += mult * (x_s S_f - x_s**2 V[i_s, f])
    dw0        += mult

``_fm_margin``'s equations, over cells. L2 on ``w`` and ``V`` as
``fm._fm_logistic_loss_builder`` states it, Adam as ``_adam.adam_update``
does, dense moments over the whole table.

- **Ingest**: ``_data.sparse_fit_columns``; float32 whatever
  ``jax_enable_x64`` says; no ``SparseVector`` is built. Rows of one
  width are the ELL block as held (``ops.sparse.one_width_block``), rows
  that keep to fields are laid one field a slot (``align_ragged_rows``),
  any other table is one block padded to its widest row.
- **Lookup and update**: under the slot plan (``ops.sparse.
  slot_block_plan``) a blocked slot's parameter rows come from its block
  by a one-hot product (``block_lookup`` with a payload axis: the rows
  ``P[idx]`` bit for bit at :data:`LOOKUP_PRECISION`) and its gradient
  goes back the same way (``block_accumulate``); the step walks the
  slots a few at a time (``block_groups``) so that no product's operand
  is long. On a TPU both are ``kernels.payload_blocks``' Mosaic kernels
  (PR 53; :func:`_walk_in_fast_memory` reads where: float32, a device's
  batch in whole tiles of 128, blocks whose parts fast memory holds): a
  tile of the batch meets a slot's block in fast memory, the looked-up
  rows stay the table's bit for bit, the cells' gradients are made in
  the kernel and summed in float32 in one fixed order; XLA's walk is
  their reference and every other backend's path. The other
  slots take their rows with ``jnp.take`` and share one ``segment_sum``
  over ``[cells, 1 + k]``: correct, and element-serial on a TPU.
- **Batches**: step ``t`` reads window ``t mod ceil(rows / batch)`` of
  the rows placed in the order ``default_rng(seed).permutation(rows)``
  (``_linear_sgd._window``), not ``_adam``'s draw with replacement: a
  draw is a gather of whole rows every step.
- **Residency**: the placed cells, labels, weights and the plan are kept
  WITH the ``Table`` (:meth:`Table.device_resident`), placed once a table
  and seed through :meth:`DeviceMesh.stage_rows`; a second fit uploads
  nothing of the table and is bit-equal at equal hyper-parameters.
- **One program**, ``fm_adam_loop``: ``max_iter``, rate, ``reg`` and
  ``tol`` are runtime operands, so a sweep over them compiles once.
- **Hand-over**: a second, small program (``fm_handover``) turns the
  learned table to the model's ``w`` and ``V [dim, k]`` on the device
  (in rows of 128 lanes, the padding last), one ``jax.device_get``
  brings them, ``w0`` and the step count to the host, and the model
  holds views of those float32 buffers: no pass over the parameters is
  made on the host (PR 56).

Spans and counters: ``docs/development/observability.md`` ("Spans",
``fm.*``; the group ``fm``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import PartitionSpec as P

from flinkml_tpu.kernels import _mosaic
from flinkml_tpu.models._adam import adam_update
from flinkml_tpu.models._data import check_binary_labels, sparse_fit_columns
from flinkml_tpu.models._linear_sgd import _placed, _slot_major, _window
from flinkml_tpu.ops import sparse
from flinkml_tpu.ops.sparse import LANES
from flinkml_tpu.parallel import DeviceMesh
from flinkml_tpu.parallel.mesh import gather_pool
from flinkml_tpu.utils.metrics import metrics
from flinkml_tpu.utils.profiling import named_program, phase, span

#: The precision of the lookup's and the accumulation's products: the
#: looked-up rows are the gathered ones bit for bit, the accumulated
#: gradient float32's own sum (one bfloat16 pass, ``DEFAULT``, rounds
#: every parameter read to 8 bits: the benchmark's control).
LOOKUP_PRECISION = jax.lax.Precision.HIGHEST
#: The step's phases (``profiling.phase``).
PHASES = ("fm.lookup", "fm.interaction", "fm.accumulate", "fm.adam")

class _Placed(NamedTuple):
    """A table's rows as the mesh holds them, in the seeded order, and
    what one look at its cells found."""

    indices: jax.Array            # [p * n_local, width] int32
    values: jax.Array             # [p * n_local, width] float32
    labels: jax.Array             # [p * n_local] float32
    weights: jax.Array            # [p * n_local] float32, 0 at the padding
    starts: jax.Array             # [width] int32 rows of 128; [1] under no plan
    slot_plan: Tuple              # a block length or None per slot; () none
    rows: int
    cells: float
    blocked_cells: float


def _ell_block(indptr, indices, values, dim: int):
    """The table's cells as ONE ELL block and its plan: ``(block,
    slot_plan, starts, blocked_cells)`` (``ops.sparse.planned_block``).
    The plan is read with a step of one row (no one-hot bounds a block's
    length: the step walks its slots), so it is the table's whatever the
    batch. A table with no plan (ragged rows that keep to no fields) is
    one block padded to its widest row (index 0, value 0), the caller's
    rows in order."""
    with gather_pool() as pool:
        block, slot_plan, starts, blocked_cells = sparse.planned_block(
            indptr, indices, values, dim, np.float32, 1, pool)
    if block is None:
        (block,), _ = sparse.pack_ell_buckets(
            indptr, indices, values, dim, max_buckets=1, dtype=np.float32)
    return block, slot_plan, starts, blocked_cells


def _place_table(indptr, indices, values, dim: int, y, w, mesh: DeviceMesh,
                 seed: int, make_room) -> _Placed:
    """Every row on the mesh, once: the plan (``hostdata.sparse_pack``),
    then the cells, labels and weights through :meth:`DeviceMesh.
    stage_rows` in the seeded order (``fm.table_to_device``), padded with
    zero rows of weight 0 to the mesh. ``make_room`` is the table's that
    will keep them (:meth:`Table.device_resident`), told their bytes
    once the block's width is known."""
    with span("hostdata.sparse_pack"):
        block, slot_plan, starts, blocked_cells = _ell_block(
            indptr, indices, values, dim)
    n, width = block["indices"].shape
    # int32 cells, float32 values, labels and weights.
    make_room(n * (8 * width + 8), mesh.mesh.devices.flat)
    with span("fm.table_to_device") as phase:
        with span("hostdata.shuffle"), span("hostdata.permute"):
            order = np.random.default_rng(seed).permutation(n)
        columns = [(block["indices"], order, np.int32),
                   (block["values"], order, np.float32),
                   (np.asarray(y), order, np.float32)]
        if w is not None:
            columns.append((np.asarray(w), order, np.float32))
        arrays = _placed(mesh.stage_rows(columns))
        if w is None:
            arrays += (mesh.shard_ones(n, np.float32),)
        sent = float(sum(a.nbytes for a in arrays[:len(columns)]))
        phase.add(bytes=sent)
    group = metrics.group("fm")
    group.counter("table_uploads")
    group.counter("table_h2d_bytes", sent)
    return _Placed(*arrays,
                   mesh.replicate(np.zeros(1, np.int32) if starts is None else starts),
                   slot_plan, n, float(np.asarray(indptr)[-1]), blocked_cells)


def _lane_rows(table):
    """``table [1 + k, dim / 128, 128]`` held to the layout its shape
    states, the 128 columns of a row along the lanes: left to itself the
    compiler lays Adam's state as some product wants it, once with the
    ``1 + k`` floats along the lanes (padded to 128: 7.5 times the bytes
    in every pass of Adam; PERF.md section 5, PR 36)."""
    return with_layout_constraint(table, Layout(major_to_minor=(0, 1, 2)))


def _walk_in_fast_memory(dtype, local_bs: int, slot_plan: Tuple, payload: int,
                         precision) -> bool:
    """Whether a plan's blocked slots are looked up and accumulated by
    :mod:`flinkml_tpu.kernels.payload_blocks` (a slot's products made,
    selected from and dropped in fast memory) and not by
    ``ops.sparse.block_lookup`` / ``block_accumulate`` through XLA: on a
    TPU (elsewhere Mosaic's kernels would run interpreted), float32
    parameters at the program's own precision (the kernels have no
    one-pass mode: a control keeps XLA's walk), a device's batch in whole
    tiles, blocks whose parts fast memory holds (a long block's, and a
    tile of all the short ones at once). Read off what the fit is handed;
    nothing sets it."""
    from flinkml_tpu.kernels import payload_blocks

    return (precision == LOOKUP_PRECISION and not _mosaic.interpret_mode()
            and payload_blocks.unsupported_reason(
                dtype, local_bs, _walk(slot_plan)[0], payload,
                len(slot_plan)) is None)


def _walk(slot_plan: Tuple):
    """The plan's blocked slots as the kernels walk them, the shortest
    blocks first: ``(lengths, where)``, each one's block length and which
    row of a step's cells it is."""
    where = sorted((j for j, length in enumerate(slot_plan) if length is not None),
                   key=lambda j: (slot_plan[j], j))
    return [slot_plan[j] for j in where], where


def xla_lookup(table, ib, vb, starts, slot_plan: Tuple, precision):
    """The blocked slots' share of a step's lookup through XLA's
    products, a few slots at a time (``ops.sparse.block_groups``):
    ``(sums [1 + k, rows], squares [rows], walked)`` for the window's
    cells ``ib``, ``vb [rows, width]``, the sums laid as
    ``kernels.payload_blocks.lookup`` hands its own (the step has one
    form of what follows); ``walked`` keeps a group's ``(length, first,
    local, xs, xp)`` for :func:`xla_accumulate`. A block is whole rows
    cut out of ``table`` and turned."""
    width = table.shape[0]
    local_bs = ib.shape[0]
    sums = jnp.zeros((local_bs, width), table.dtype)
    squares = jnp.zeros((local_bs,), table.dtype)
    walked = []
    for length, slots in sparse.block_groups(slot_plan, local_bs, width):
        first = [starts[j] for j in slots]
        blocks = jnp.stack([
            jax.lax.dynamic_slice_in_dim(table, at, length // LANES, axis=1)
            .reshape(width, length).T for at in first])
        local = _slot_major(ib, slots) - LANES * jnp.stack(first)[:, None]
        xs = _slot_major(vb, slots)
        xp = xs[..., None] * sparse.block_lookup(blocks, local, precision)
        sums += jnp.sum(xp, axis=0)
        squares += jnp.sum(jnp.square(xp[..., 1:]), axis=(0, 2))
        walked.append((length, first, local, xs, xp))
    return sums.T, squares, walked


def xla_accumulate(grad, walked, cell_grads, precision):
    """:func:`xla_lookup`'s transpose: every walked group's cells'
    gradients (``cell_grads(xs, xp)`` over ``[slots, rows]``) summed on
    their blocks' columns and added into ``grad [1 + k, dim_pad / 128,
    128]``."""
    width = grad.shape[0]
    for length, first, local, xs, xp in walked:
        back = sparse.block_accumulate(
            local, cell_grads(xs, xp), length, precision)
        # One slot after another: blocks may overlap, and add.
        for at, slot_grad in zip(first, back):
            grad = _add_rows(grad, slot_grad.T.reshape(width, -1, LANES), at)
    return grad


def _add_rows(grad, slot_grad, at):
    """``grad`` with ``slot_grad [1 + k, rows, 128]`` added from row
    ``at`` of 128 columns on."""
    return jax.lax.dynamic_update_slice_in_dim(
        grad,
        jax.lax.dynamic_slice_in_dim(grad, at, slot_grad.shape[1], axis=1)
        + slot_grad, at, axis=1)


def make_step(logistic: bool, local_bs: int, axis: str, slot_plan: Tuple,
              precision=LOOKUP_PRECISION):
    """ONE Adam step of the sparse factorization machine on a device's
    shard: ``step(params, m, v, t, idx, val, y, wt, starts, lr, reg) ->
    (params, m, v, loss)``, ``params = (w0 [1], table [1 + k, dim_pad /
    128, 128])``, ``t`` the global 0-based step (the window and Adam's
    bias correction), ``starts`` the blocks' first rows of 128 columns
    (any array under an empty plan). The table lies with its COLUMNS in
    rows of 128 lanes, a float of the payload a plane (a TPU pads a
    ``[dim, 17]`` array's rows to 128 lanes, and gave ``[17, dim]`` the
    same layout: 7.5 times the bytes in every pass of Adam); XLA's walk
    cuts a block out of it as whole rows and turns it. The module
    docstring has the equations.

    Where :func:`_walk_in_fast_memory` says so the blocked slots' lookup
    and accumulation are ``kernels.payload_blocks``' two kernels: the
    same looked-up floats, the gradient's float32 sums in the kernel's
    fixed order. That chooses who makes ``sums`` and ``grad`` and nothing
    else: the rows' sums, the interaction and ``base`` lie ``[1 + k,
    rows]`` (the batch along the lanes, as the kernels take them) on
    either path."""
    from flinkml_tpu.kernels import payload_blocks

    def step(params, m, v, t, idx, val, y, wt, starts, lr, reg):
        w0, table = params
        width = table.shape[0]
        fused = _walk_in_fast_memory(table.dtype, local_bs, slot_plan, width,
                                     precision)
        with phase("fm.lookup"):
            ib, vb = _window(idx, t, local_bs), _window(val, t, local_bs)
            yb, wb = _window(y, t, local_bs), _window(wt, t, local_bs)
            general = [j for j in range(ib.shape[1])
                       if j >= len(slot_plan) or slot_plan[j] is None]
            # What a cell adds to its row's sums is x_s P[i_s]: kept, slot
            # major, for the gradient.
            if fused:
                # The kernels walk the window's cells as they are, a slot
                # a row: which rows, length by length, and the starts say.
                walk = _walk(slot_plan)
                whole = (ib.T, vb.T, starts)
                xps, sums, squares = payload_blocks.lookup(*walk, table, *whole)
            else:
                sums, squares, walked = xla_lookup(
                    table, ib, vb, starts, slot_plan, precision)
            if general:
                ig, xg = _slot_major(ib, general), _slot_major(vb, general)
                gp = xg[..., None] * jnp.moveaxis(
                    jnp.take(table.reshape(width, -1), ig, axis=1), 0, -1)
                sums += jnp.sum(gp, axis=0).T
                squares += jnp.sum(jnp.square(gp[..., 1:]), axis=(0, 2))
        with phase("fm.interaction"):
            linear, factor_sums = sums[0], sums[1:]
            margin = w0[0] + linear + 0.5 * (
                jnp.sum(jnp.square(factor_sums), axis=0) - squares)
            if logistic:
                per_row = jnp.logaddexp(0.0, margin) - yb * margin
                mult = (jax.nn.sigmoid(margin) - yb) * wb
            else:
                err = margin - yb
                per_row, mult = 0.5 * err * err, err * wb
            # d y^ / d P[i_s] = x_s (1, S_f - x_s V[i_s, f]).
            base = jnp.concatenate([jnp.ones_like(linear)[None], factor_sums])
            factors_only = jnp.arange(width) > 0

        def cell_grads(xs, xp):
            """``xs [slots, rows]`` and ``xp [slots, rows, 1 + k]``."""
            return (mult * xs)[..., None] * (
                base.T - jnp.where(factors_only, xp, 0))

        with phase("fm.accumulate"):
            if general:
                # Summed a row's cells after another's, as they always were.
                grad = jax.ops.segment_sum(
                    jnp.swapaxes(cell_grads(xg, gp), 0, 1).reshape(-1, width),
                    ig.T.reshape(-1),
                    num_segments=table.shape[1] * LANES).T.reshape(table.shape)
            else:
                grad = jnp.zeros_like(table)
            if fused:
                # One slot after another: blocks may overlap, and add.
                for j, slot_grad in zip(walk[1], payload_blocks.accumulate(
                        *walk, *whole, mult, base, xps)):
                    grad = _add_rows(grad, slot_grad, starts[j])
            else:
                grad = xla_accumulate(grad, walked, cell_grads, precision)
        with phase("fm.adam"):
            wsum = jax.lax.psum(jnp.sum(wb), axis)
            total_w = jnp.maximum(wsum, 1e-12)
            # L2 as fm._fm_*_loss_builder states it: reg * (|w|^2 + |V|^2)
            # times the batch's weight, inside the sum that total_w divides.
            l2 = reg * wsum / total_w
            loss = (jax.lax.psum(jnp.sum(per_row * wb), axis) / total_w
                    + l2 * jnp.sum(jnp.square(table)))
            grads = (jax.lax.psum(jnp.sum(mult), axis)[None] / total_w,
                     _lane_rows(jax.lax.psum(grad, axis)) / total_w + 2.0 * l2 * table)
            params, m, v = adam_update(params, m, v, grads, t, lr)
            (w0, table), (m0, m1), (v0, v1) = params, m, v
            return ((w0, _lane_rows(table)), (m0, _lane_rows(m1)),
                    (v0, _lane_rows(v1)), loss)

    return step


@functools.lru_cache(maxsize=32)
def _trainer(mesh, logistic: bool, local_bs: int, axis: str, slot_plan: Tuple,
             precision=LOOKUP_PRECISION):
    """Adam's whole run as one program, ``fm_adam_loop``: ``(w0, table
    [1 + k, dim_pad / 128, 128], idx, val, y, wt, starts, lr, reg, max_iter, tol) ->
    (w0, table, steps, loss)``. It stops after ``max_iter`` steps, or when two
    successive losses lie within ``tol`` of each other as ``_adam``'s
    loop does; at ``tol`` 0 it runs ``max_iter`` steps exactly unless the
    loss is NaN (two losses equal to the bit do not stop it)."""
    step = make_step(logistic, local_bs, axis, slot_plan, precision)

    def per_device(w0, table, idx, val, y, wt, starts, lr, reg, max_iter, tol):
        params = (w0, table)
        zeros = jax.tree.map(jnp.zeros_like, params)

        def cond(state):
            t, _, _, _, prev, cur = state
            moving = jnp.where(tol > 0, jnp.abs(prev - cur) > tol,
                               ~jnp.isnan(cur))
            return (t < max_iter) & moving

        def body(state):
            t, params, m, v, _, last = state
            params, m, v, loss = step(params, m, v, t, idx, val, y, wt,
                                      starts, lr, reg)
            return t + 1, params, m, v, last, loss

        inf = jnp.asarray(jnp.inf, table.dtype)
        t, params, _, _, _, loss = jax.lax.while_loop(
            cond, body, (jnp.asarray(0, jnp.int32), params, zeros, zeros,
                         inf, -inf))
        return params[0], params[1], t, loss

    return jax.jit(jax.shard_map(
        named_program("fm_adam_loop", per_device, phases=PHASES), mesh=mesh,
        in_specs=(P(), P()) + (P(axis),) * 4 + (P(),) * 5,
        out_specs=(P(), P(), P(), P()),
    ))


def _model_layout(table):
    """The learned ``table [1 + k, dim_pad / 128, 128]`` as the model
    holds it, in rows of 128 lanes: ``(w [dim_pad / 128, 128], V [dim_pad
    * k / 128, 128])``, ``V`` a column's ``k`` factors side by side. Read
    flat, each is the model's array and then the padding past ``dim``, so
    the host's cut and ``reshape(dim, k)`` are views. Not ``[dim, k]``
    itself: a TPU pads the ``k`` to 128 lanes and hands the host a layout
    it has to turn again; and not flat: a one-axis array of 64 MB crosses
    the link three times slower than the same bytes in rows (0.072 s for
    0.025: PERF.md section 5, PR 56)."""
    factors = table[1:].reshape(table.shape[0] - 1, -1)
    return table[0], factors.T.reshape(-1, LANES)


#: Dispatched straight after ``fm_adam_loop``, before the wait: the turn
#: the host made of the read-back (a strided pass over ``dim * k`` floats)
#: is the device's, outside the loop and outside every phase.
_handover = jax.jit(named_program("fm_handover", _model_layout))


def padded_dim(dim: int) -> int:
    """``dim`` rounded up to whole rows of 128 columns: the parameter
    table's columns, those past ``dim`` zero for good (no cell names them,
    and Adam leaves a zero with a zero gradient where it is)."""
    return -(-dim // LANES) * LANES


def fit_csr(est, table, logistic: bool, precision=LOOKUP_PRECISION):
    """``FMClassifier.fit`` / ``FMRegressor.fit`` where the features
    column is a ``CsrColumn``: ``(w0 [1], w [dim], V [dim, k])`` as
    float32 host arrays, C-contiguous and read-only: views of the buffers
    one ``jax.device_get`` returned, laid as the model holds them by the
    device (:func:`_model_layout`), with no pass over them on the host. The caller's span ``fit`` holds all of
    it. ``precision`` is the benchmark's control's alone."""
    features_col = est.get(est.FEATURES_COL)
    label_col, weight_col = est.get(est.LABEL_COL), est.get(est.WEIGHT_COL)
    indptr, indices, values, dim, labels, w = sparse_fit_columns(
        table, features_col, label_col, weight_col)
    if indptr.size - 1 == 0:
        raise ValueError("training table is empty")
    if logistic:
        check_binary_labels(labels, type(est).__name__)
    mesh = est.mesh or DeviceMesh()
    seed = est.get_seed()
    # The step may hold the payload kernels (a TPU's): what tracing them
    # imports loads beside the plan's pass and the permutation.
    _mosaic.import_beside_host_work()
    placed = table.device_resident(
        ("fm_rows_on_mesh", features_col, label_col, weight_col,
         mesh.mesh, "float32", seed),
        lambda make_room: _place_table(
            indptr, indices, values, dim, labels.values, w, mesh, seed,
            make_room))
    p = mesh.axis_size()
    n_local = placed.indices.shape[0] // p
    local_bs = min(max(1, -(-est.get(est.GLOBAL_BATCH_SIZE) // p)), n_local)
    with span("fm.init"):
        w0, w_start, v_start, reg = est._params0(dim)
        start = jnp.pad(
            jnp.concatenate([w_start[None, :], v_start.T], axis=0),
            ((0, 0), (0, padded_dim(dim) - dim))).reshape(
                v_start.shape[1] + 1, -1, LANES)
        w0, start = mesh.replicate((w0, start))
    trainer = _trainer(mesh.mesh, logistic, local_bs, DeviceMesh.DATA_AXIS,
                       placed.slot_plan, precision)
    with span("fm.loop"):
        with span("fm.dispatch"):
            w0, learned, steps, _ = trainer(
                w0, start, placed.indices, placed.values, placed.labels,
                placed.weights, placed.starts,
                np.float32(est.get(est.LEARNING_RATE)), np.asarray(reg)[0],
                np.int32(est.get(est.MAX_ITER)),
                np.float32(est.get(est.TOL)))
        out = (w0, *_handover(learned), steps)
        # The caller reads the parameters next: waiting here costs
        # nothing and gives the loop a span its device time lies in.
        jax.block_until_ready(out)
    with span("fm.readback"):
        w0, weights, factors, steps = jax.device_get(out)
        # Views: the padding past ``dim`` cut, a column's factors a row.
        k = learned.shape[0] - 1
        weights = weights.reshape(-1)[:dim]
        factors = factors.reshape(-1)[:dim * k].reshape(dim, k)
    group = metrics.group("fm")
    group.counter("fits")
    group.counter("steps", float(steps))
    group.counter("rows", float(placed.rows))
    group.counter("cells", placed.cells)
    group.counter("blocked_cells", placed.blocked_cells)
    # Counted at the loop, as ``trainer.fused_block_fits`` is: 1.0 a fit
    # whose blocked slots ran in ``kernels.payload_blocks``.
    group.counter("fused_block_fits", float(_walk_in_fast_memory(
        start.dtype, local_bs, placed.slot_plan, start.shape[0], precision)))
    return w0, weights, factors


def csr_margin(csr, w0: float, w: np.ndarray, v: np.ndarray,
               chunk_rows: int = 1 << 16) -> np.ndarray:
    """The model's margin for every row of a ``CsrColumn``, from its
    arrays (no ``SparseVector`` is built): NumPy at float64, the
    parameters widened as gathered (a fit's are float32: the product of
    a float64 value and a gathered float32 parameter is the product with
    its float64 value), a chunk of rows at a time, so ``w`` and ``v`` are
    never widened whole."""
    if csr.dim != w.shape[0]:
        raise ValueError(
            f"sparse features have dim {csr.dim}, model expects {w.shape[0]}")
    indptr = np.asarray(csr.indptr, np.int64)
    n = indptr.size - 1
    out = np.full(n, w0, np.float64)
    for lo in range(0, n, chunk_rows):
        hi = min(lo + chunk_rows, n)
        cells = slice(indptr[lo], indptr[hi])
        idx, x = csr.indices[cells], csr.values[cells].astype(np.float64)
        row = np.repeat(np.arange(hi - lo), np.diff(indptr[lo:hi + 1]))
        xv = x[:, None] * v[idx]
        per_row = x * w[idx] - 0.5 * np.sum(xv * xv, axis=1)
        out[lo:hi] += np.bincount(row, weights=per_row, minlength=hi - lo)
        for f in range(v.shape[1]):
            s_f = np.bincount(row, weights=xv[:, f], minlength=hi - lo)
            out[lo:hi] += 0.5 * s_f * s_f
    return out
