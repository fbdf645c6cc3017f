"""Generic distributed SGD for linear models (dense and sparse).

One trainer serves LogisticRegression, LinearSVC, and LinearRegression: the
models differ only in ``d loss/d margin``, so the loss enters as a static
key selecting a margin-gradient function, and everything else — window
slicing, MXU matvec, ``psum``, proximal update, ``lax.while_loop``
termination — is shared. This is the TPU inversion of the reference's
``CacheDataAndDoTrain`` machinery (``LogisticRegression.java:334-397``);
see ``logistic_regression.py`` for the full mapping.

Losses (margins use labels y ∈ {0,1} mapped to ys = 2y-1 where relevant):
  - ``logistic``: loss = w·log(1+exp(-dot·ys)); matches
    ``LogisticGradient.java:50-96``.
  - ``hinge`` (LinearSVC): loss = w·max(0, 1 - dot·ys).
  - ``squared`` (LinearRegression): loss = w·(dot - y)²/2.

Regularization: L2 enters the gradient; L1 (elastic net) is applied as a
proximal soft-threshold after the gradient step — the "proximal SGD step"
of BASELINE.json config #3.

The sparse path consumes padded ELL batches (``flinkml_tpu.ops.sparse``):
forward = gather+row-sum, gradient = flat segment-sum scatter — the
Criteo-scale path (config #5). Where a table's ELL slots each keep to a
short range of columns (one cell a field, the fields on blocks of the
feature vector), those slots do without the gather and the scatter: a
per-slot plan read off the cells in every fit, and a step that follows it
(:func:`make_sparse_step_bucketed`).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

# The structural CSR check the sparse stream paths run in pass 0: the one
# a ``CsrColumn`` runs at construction (without its row-order part).
from flinkml_tpu.linalg import check_csr_structure as _check_csr_structure
from flinkml_tpu.ops.losses import margin_terms as _margin_grad
from flinkml_tpu.ops.sparse import (
    LANES,
    block_accumulate,
    block_groups,
    block_lookup,
    ell_matvec,
    pack_ell_buckets,
    planned_block,
)
from flinkml_tpu.parallel import DeviceMesh
from flinkml_tpu.parallel.mesh import gather_pool
from flinkml_tpu.utils.metrics import metrics
from flinkml_tpu.utils.profiling import named_program, phase, span

_LOSS_KEYS = ("logistic", "hinge", "squared")


def _soft_threshold(x, t):
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)


def _acc_dt(dt):
    """Reduction dtype: sub-f32 data accumulates in f32. A stepwise bf16
    sum saturates absurdly early (32768 unit weights sum to 256), which
    would corrupt ``step_size = lr / wsum`` and the loss criterion."""
    return jnp.float32 if jnp.dtype(dt).itemsize < 4 else jnp.dtype(dt)


def align_local_bs(global_batch_size: int, p_size: int, n_local: int) -> int:
    """Per-device batch: ceil(global/p), clamped to the shard — the
    requested batch is honored exactly, no silent inflation."""
    return min(max(1, math.ceil(global_batch_size / p_size)), n_local)


def _window_start(n_rows: int, epoch, local_bs: int):
    """Row the window of step ``epoch`` starts at, before any clamping:
    window ``epoch mod ceil(n_rows / local_bs)``."""
    n_windows = max(-(-n_rows // local_bs), 1)
    return (jnp.asarray(epoch, jnp.int32) % n_windows) * local_bs


def _window(arr, epoch, local_bs):
    """Contiguous rotating window with ceil coverage (tail included via
    dynamic_slice clamping)."""
    start = _window_start(arr.shape[0], epoch, local_bs)
    zero = jnp.zeros((), dtype=start.dtype)
    if arr.ndim == 1:
        return jax.lax.dynamic_slice(arr, (start,), (local_bs,))
    return jax.lax.dynamic_slice(arr, (start, zero), (local_bs, arr.shape[1]))


def _reach_rows(n_local: int, local_bs: int, first: int, last: int) -> int:
    """One past the last local row that steps ``[first, last)`` read
    through :func:`_window` (its arithmetic, tail clamping included):
    what a placement has to send for them. Step ``k`` reads window ``k
    mod ceil(n_local / local_bs)``; a span of steps that wraps, or takes
    the last window (clamped to end at the shard's end), reaches it all."""
    n_windows = max(-(-n_local // local_bs), 1)
    if last <= first:
        return 0
    top = first % n_windows + (last - first)  # one past the highest window
    return n_local if top >= n_windows else top * local_bs


def _steps_ready(n_local: int, local_bs: int, first: int, last: int,
                 complete: int) -> int:
    """The epoch the loop can run to from ``first`` (at most ``last``)
    when the leading ``complete`` local rows of every shard have landed:
    every step before it reads a window that is whole."""
    if complete >= n_local:
        return last
    n_windows = max(-(-n_local // local_bs), 1)
    # complete < n_local: whole windows only, none of them the clamped last
    ahead = complete // local_bs - first % n_windows
    return min(last, first + max(0, ahead))


def _follow_windows(place_rows, n_local: int, local_bs: int):
    """The loop's demand on a placement. ``place_rows(reach_rows)`` gives
    the rounds of ``(arrays, complete local rows)`` (:meth:`DeviceMesh.
    stage_rows`); the result is what :func:`_run_chunked` takes:
    ``place(first, last)``, the rounds of ``(arrays, epoch the loop can
    run to)`` for steps ``[first, last)``, which stop at the last row
    those steps can read."""

    def place(first: int, last: int):
        rounds = place_rows(_reach_rows(n_local, local_bs, first, last))
        return ((arrays, _steps_ready(n_local, local_bs, first, last, complete))
                for arrays, complete in rounds)

    return place


def _placed(rounds) -> Tuple:
    """A placement run to its last round: the arrays, whole."""
    *_, (arrays, _) = rounds
    return arrays


class _Kept(NamedTuple):
    """A fit's placement run to its last round, as its table keeps it."""

    arrays: Tuple             # what the loop was handed
    reaches: Tuple[int, ...]  # local rows each bucket is complete to
    plan: Tuple               # sparse: (local_bss, slot_plan, local rows a bucket)


def table_placements(table, features_col: str, label_col: str,
                     weight_col: Optional[str]):
    """What the trainers take as ``kept``: ``kept(tag, *rest)`` is the
    table's :class:`~flinkml_tpu.table.ResidentSlot` for a placement of
    these columns, the trainer adding what else its arrays depend on
    (the mesh, the placed dtype, the seed; the sparse fit its batch and
    bucket count). Rate, ``reg``, ``tol`` and ``max_iter`` are the loop's
    operands and key nothing."""

    def kept(tag: str, *rest):
        return table.resident(
            (tag, features_col, label_col, weight_col) + rest)

    return kept


def _find_or_keep(slot, place, windows, nbytes: int, mesh: DeviceMesh,
                  plan: Tuple = ()):
    """``place`` as a table that keeps placements answers it (``slot``
    None, a caller with arrays and no table: ``place`` itself).
    ``windows`` is ``(local rows, local batch)`` a bucket, ``nbytes``
    what the placement puts on the mesh.

    A hit: the table holds a placement under the slot's key complete as
    far as steps ``[first, last)`` read (:func:`_reach_rows`), and it is
    the ONE round, ready to ``last``: nothing is permuted, gathered or
    sent, and :func:`_run_chunked` makes one dispatch. Anything else is a
    miss, the fit as it is without a table: the rounds of ``place``,
    after the set of kept placements has made room for them (an entry
    that fell short goes first), kept once the last round has been
    handed out and asked past, so a fit that raises before then keeps
    nothing. ``metrics.group("hostdata")`` counts ``placement_hits`` and
    ``placement_misses``."""
    if slot is None:
        return place

    def found_or_placed(first: int, last: int):
        need = tuple(_reach_rows(rows, bs, first, last) for rows, bs in windows)
        kept = slot.find()
        hit = kept is not None and all(
            n <= have for n, have in zip(need, kept.reaches))
        counts = metrics.group("hostdata")
        counts.counter("placement_hits", float(hit))
        counts.counter("placement_misses", float(not hit))
        if hit:
            return iter([(kept.arrays, last)])
        slot.make_room(nbytes, mesh.mesh.devices.flat)
        # Started here, as without a table: the order is computed before
        # the loop's span opens, the rounds inside it.
        return keep_whole(place(first, last), need)

    def keep_whole(rounds, need):
        for arrays, ready in rounds:
            yield arrays, ready
        slot.keep(_Kept(arrays, need, plan))

    return found_or_placed


def _rows_in_fast_memory(xl, coef, local_bs: int) -> bool:
    """Whether a dense step's window is read ONCE, by
    :mod:`flinkml_tpu.kernels.dense_step` (a tile of rows makes its
    margins, multipliers and gradient while it is in fast memory), and
    not twice, by XLA's forward and back products: on a TPU, float32
    rows and coefficients, a shard in whole windows of whole tiles, a
    width the vector unit's products are for. Read off what the step is
    handed; nothing sets it."""
    from flinkml_tpu.kernels import dense_step

    return (coef.dtype == xl.dtype and dense_step.unsupported_reason(
        xl.dtype, xl.shape[0], local_bs, xl.shape[1]) is None)


def make_dense_step(loss: str, local_bs: int, axis: str):
    """Per-device epoch: window → margins, multipliers, gradient → psum
    → prox update.

    Where :func:`_rows_in_fast_memory` says so the window's three sums
    are ``kernels.dense_step.margin_grad``'s, its rows read from the
    shard in place and once; everywhere else XLA's forward and back
    products, each a pass over a ``dynamic_slice`` of the shard. On a
    v5e at ``lr-a9a.fit``'s 262,144 x 123 float32 XLA's pair is two
    ``multiply_reduce`` fusions at 0.182 and 0.180 ms a step, nine
    tenths of the HBM rate each (ledger, PR 39); the kernel's one read
    is 0.199 ms a step, one read of the window at 80 % of the HBM's
    rate (chip runs, PR 40; PERF.md section 5). The hand-fused
    Pallas step this file once had streamed the rows through the MXU
    and lost to XLA's pair; this one loads them as its weights."""
    from flinkml_tpu.kernels import dense_step

    def step(coef, epoch, xl, yl, wl, learning_rate, reg_l2, reg_l1):
        acc = _acc_dt(xl.dtype)
        if _rows_in_fast_memory(xl, coef, local_bs):
            grad_l, loss_l, wsum_l = dense_step.margin_grad(
                loss, xl, yl, wl, coef,
                _window_start(xl.shape[0], epoch, local_bs), local_bs)
        else:
            xb = _window(xl, epoch, local_bs)
            yb = _window(yl, epoch, local_bs)
            wb = _window(wl, epoch, local_bs)
            dot = xb @ coef
            mult, per_ex = _margin_grad(loss, dot, yb, wb)
            grad_l = xb.T @ mult
            loss_l = jnp.sum(per_ex.astype(acc))
            wsum_l = jnp.sum(wb.astype(acc))
        grad = jax.lax.psum(grad_l, axis)
        loss_sum = jax.lax.psum(loss_l, axis)
        wsum = jax.lax.psum(wsum_l, axis)
        grad = grad + 2.0 * reg_l2 * coef
        loss_sum = loss_sum + reg_l2 * jnp.sum(jnp.square(coef.astype(acc)))
        step_size = learning_rate.astype(acc) / wsum
        new_coef = _soft_threshold(
            coef - step_size.astype(coef.dtype) * grad,
            step_size.astype(coef.dtype) * reg_l1,
        )
        return new_coef, (loss_sum / wsum).astype(coef.dtype)

    return step


#: The sparse step's phases (``profiling.phase``): the coefficients of a
#: window's cells looked up, and their gradient accumulated, each by the
#: block products (kernel or XLA) with the gather or segment-sum of the
#: slots no block takes; and the all-reduce of the local gradient and
#: the two local sums over the data axis (on a mesh of one the compiler
#: drops it, and the phase holds no operation). The margin's parts, the
#: L2 term and the update are in none.
SPARSE_PHASES = ("lr.sparse_lookup", "lr.sparse_accumulate", "lr.psum")


def _lane_rows(x):
    """``x [dim]`` as rows of 128 lanes, zeros past its end: what a
    block, which starts at a row, is cut out of."""
    rows = -(-x.shape[0] // LANES)
    return jnp.pad(x, (0, rows * LANES - x.shape[0])).reshape(rows, LANES)


def _slot_major(block, slots):
    """``block[:, slots].T`` as static slices (an index array would be a
    gather): ``[len(slots), rows]``, the rows along the lanes."""
    if not slots:
        return block[:, :0].T
    return jnp.stack([block[:, j] for j in slots])


def _blocks_in_fast_memory(dtype, local_bs: int, slot_plan: Tuple) -> bool:
    """Whether a plan's blocked slots are looked up and accumulated by
    :mod:`flinkml_tpu.kernels.sparse_blocks` (a slot's one-hot product
    made, selected from and dropped in fast memory) and not by
    ``ops.sparse.block_lookup`` / ``block_accumulate`` through XLA: on a
    TPU (elsewhere Mosaic's kernels would run interpreted), float32
    coefficients, a device's batch in whole tiles, blocks that fast
    memory holds. Read off what the fit is handed; nothing sets it."""
    from flinkml_tpu.kernels import _mosaic, sparse_blocks

    groups = [(length, len(slots))
              for length, slots in block_groups(slot_plan, local_bs)]
    return (bool(groups) and not _mosaic.interpret_mode()
            and sparse_blocks.unsupported_reason(dtype, local_bs, groups) is None)


def make_sparse_step_bucketed(loss: str, local_bss: Tuple[int, ...],
                              axis: str, dim: int,
                              slot_plan: Tuple = ()):
    """nnz-bucketed sparse (padded-ELL) step: gather forward, one fused
    segment-sum gradient over every bucket's cells; under a plan, the
    slots whose columns sit in a block of their own do without either.

    The batch is stratified across the nnz buckets (``ops.sparse.
    pack_ell_buckets``): each bucket contributes a window sized
    proportionally to its row count, so every step sees a representative
    nnz mix and every epoch covers every bucket's rows. The data args
    are four sharded arrays a bucket (indices, values, y, w).

    ``slot_plan`` is what :func:`prepare_sparse_buckets` read off a
    one-width table's cells (``ops.sparse.slot_block_plan``: a block
    length or None per ELL slot; the one bucket's). Under a plan the
    step takes one more array after the bucket's four, the blocks'
    starts (``[width] int32`` in rows of 128 columns, a runtime operand:
    tables of one schema share the program wherever their columns
    start). A blocked slot's block is ``length / 128`` consecutive rows
    of the coefficients laid 128 a row; its cells' coefficients are
    looked up in it by a one-hot product (``ops.sparse.block_lookup``:
    the values ``coef[idx]`` bit for bit) and its gradient accumulated
    the same way (``block_accumulate``) and added to those rows; the
    other slots gather and share the one segment-sum, as every slot does
    under an empty plan, whose program is the one this function built
    before there were plans. Blocks may overlap: the gradient adds.

    Where :func:`_blocks_in_fast_memory` says so the two block products
    are ``kernels.sparse_blocks``' two kernels, every group's slots in
    one call each side of ``_margin_grad``: the same looked-up floats,
    the gradient's float32 sums in the kernel's fixed order. The row
    gather before and the row scatter-add after are the same."""
    from flinkml_tpu.kernels import sparse_blocks

    if any(slot_plan) and len(local_bss) != 1:
        raise ValueError("a slot plan is a one-bucket table's")
    groups = block_groups(slot_plan, local_bss[0])
    general = [j for j, length in enumerate(slot_plan) if length is None]

    def step(coef, epoch, *rest):
        *blocks, learning_rate, reg_l2, reg_l1 = rest
        if groups:
            *blocks, slot_starts = blocks
        acc = _acc_dt(coef.dtype)
        contribs, flat_idx, block_grads = [], [], []
        loss_l = jnp.zeros((), acc)
        wsum_l = jnp.zeros((), acc)
        for b, local_bs in enumerate(local_bss):
            idxl, vall, yl, wl = blocks[4 * b : 4 * (b + 1)]
            ib = _window(idxl, epoch, local_bs)
            vb = _window(vall, epoch, local_bs)
            yb = _window(yl, epoch, local_bs)
            wb = _window(wl, epoch, local_bs)
            fused = _blocks_in_fast_memory(coef.dtype, local_bs, slot_plan)
            with phase("lr.sparse_lookup"):
                # Each group's block rows and, for XLA's products, its
                # cells, slot-major, indexed from their blocks' first
                # rows; what is left of ib, vb are the general slots.
                cells = []
                for length, slots in groups:
                    first = jnp.stack([slot_starts[j] for j in slots])
                    rows = first[:, None] + jnp.arange(length // LANES)
                    cells.append((rows, None, None) if fused else (
                        rows, _slot_major(ib, slots) - LANES * first[:, None],
                        _slot_major(vb, slots)))
                if fused:
                    # The kernels walk the window's cells as they are, a
                    # slot a row: which rows, group by group, and the
                    # starts say.
                    walk = ([(length, len(slots)) for length, slots in groups],
                            [j for _, slots in groups for j in slots])
                    whole = (ib.T, vb.T, slot_starts)
                if groups:
                    ib, vb = _slot_major(ib, general).T, _slot_major(vb, general).T
                    tiled = _lane_rows(coef)
                dot = ell_matvec(ib, vb, coef)
                if fused:
                    dot = dot + sparse_blocks.lookup_dot(
                        *walk, [tiled[rows] for rows, _, _ in cells], *whole)
                else:
                    for rows, local, vals in cells:
                        looked = block_lookup(
                            tiled[rows].reshape(rows.shape[0], -1), local)
                        dot = dot + jnp.sum(vals * looked, axis=0)
            mult, per_ex = _margin_grad(loss, dot, yb, wb)
            with phase("lr.sparse_accumulate"):
                if fused:
                    block_grads += zip(
                        (rows for rows, _, _ in cells),
                        sparse_blocks.accumulate(*walk, *whole, mult))
                else:
                    block_grads += [
                        (rows, block_accumulate(
                            local, vals * mult[None, :], LANES * rows.shape[1]))
                        for rows, local, vals in cells]
                contribs.append((vb * mult[:, None]).reshape(-1))
                flat_idx.append(ib.reshape(-1))
            loss_l = loss_l + jnp.sum(per_ex.astype(acc))
            wsum_l = wsum_l + jnp.sum(wb.astype(acc))
        with phase("lr.sparse_accumulate"):
            grad_local = jax.ops.segment_sum(
                jnp.concatenate(contribs), jnp.concatenate(flat_idx),
                num_segments=dim,
            )
            if block_grads:
                tiled = _lane_rows(grad_local)
                for rows, sums in block_grads:
                    tiled = tiled.at[rows].add(sums.reshape(rows.shape + (LANES,)))
                grad_local = tiled.reshape(-1)[:dim]
        with phase("lr.psum"):
            grad = jax.lax.psum(grad_local, axis)
            loss_sum = jax.lax.psum(loss_l, axis)
            wsum = jax.lax.psum(wsum_l, axis)
        grad = grad + 2.0 * reg_l2 * coef
        loss_sum = loss_sum + reg_l2 * jnp.sum(jnp.square(coef.astype(acc)))
        step_size = learning_rate.astype(acc) / wsum
        new_coef = _soft_threshold(
            coef - step_size.astype(coef.dtype) * grad,
            step_size.astype(coef.dtype) * reg_l1,
        )
        return new_coef, (loss_sum / wsum).astype(coef.dtype)

    return step


def _whole_loop(mesh, step, n_sharded: int, axis: str, name: str,
                phases: Tuple[str, ...] = ()):
    """Carry-style whole-loop trainer around one per-device ``step``: runs
    epochs from ``epoch`` up to ``epoch_end`` (or until ``loss <= tol``)
    entirely on device and returns the full carry ``(coef, epoch, loss)``.
    The data args are ``n_sharded`` arrays sharded along ``axis``; the
    program's ``name`` is what a profile calls each dispatch of it, and
    ``phases`` are those ``step`` opens (``profiling.named_program``).

    Because the carry and ``epoch_end`` are runtime values, the SAME
    compiled executable serves both the one-dispatch fit (epoch_end =
    max_iter) and the chunked fault-tolerant fit (K epochs per dispatch,
    carry snapshot between dispatches) — so a chunked/resumed run is
    bit-identical to the uninterrupted run by construction. This is the
    TPU-native answer to the reference's always-on mid-iteration
    checkpointing (``Checkpoints.java:43-211``): the unit of recovery is
    the dispatch, and the only state is the carry."""

    def per_device(coef, epoch, cur_loss, *rest):
        data = rest[:n_sharded]
        learning_rate, reg_l2, reg_l1, tol, epoch_end = rest[n_sharded:]

        def cond(carry):
            _, ep, cur = carry
            return jnp.logical_and(ep < epoch_end, cur > tol)

        def body(carry):
            c, ep, _ = carry
            new_coef, mean_loss = step(
                c, ep, *data, learning_rate, reg_l2, reg_l1
            )
            return new_coef, ep + 1, mean_loss

        return jax.lax.while_loop(cond, body, (coef, epoch, cur_loss))

    return jax.jit(
        jax.shard_map(
            named_program(name, per_device, phases),
            mesh=mesh,
            in_specs=(P(), P(), P()) + (P(axis),) * n_sharded + (P(),) * 5,
            out_specs=(P(), P(), P()),
        )
    )


@functools.lru_cache(maxsize=128)
def _dense_trainer(mesh, loss: str, local_bs: int, axis: str):
    """The dense whole-loop trainer (:func:`_whole_loop`)."""
    return _whole_loop(mesh, make_dense_step(loss, local_bs, axis), 3, axis,
                       "lr_dense_loop")


@functools.lru_cache(maxsize=128)
def _sparse_trainer_bucketed(mesh, loss: str, local_bss: Tuple[int, ...],
                             axis: str, dim: int,
                             slot_plan: Tuple = ()):
    """The bucketed sparse whole-loop trainer (:func:`_whole_loop`) over
    four sharded arrays a bucket. ``slot_plan`` (:func:`make_sparse_
    step_bucketed`) is lru-key material: static, a block length or None
    per slot, up a short ladder so that a configuration's tables share
    one; where the blocks
    start is a fifth sharded array (every device's shard the same
    ``[width]`` starts) and keys nothing. A table with no blocked slot
    has the empty plan ``()``, no fifth array, and the program every
    sparse fit had before."""
    step = make_sparse_step_bucketed(loss, local_bss, axis, dim, slot_plan)
    return _whole_loop(mesh, step, 4 * len(local_bss) + bool(slot_plan), axis,
                       "lr_sparse_loop", SPARSE_PHASES)


def _restore_carry(checkpoint_manager, dim: int, dtype, mesh=None):
    """Restore the latest ``(coef, loss)`` carry; returns
    ``(coef_host, epoch, loss)`` or None. One definition shared by the
    dense chunked path and the stream path so the checkpoint payload shape
    can never silently diverge between them.

    Restores through :func:`stream_sync.agreed_restore_latest` so a
    rank-local failure aborts every rank instead of stranding the peers
    in the training collectives; a ``None`` return means genuinely no
    checkpoint."""
    from flinkml_tpu.iteration.stream_sync import agreed_restore_latest

    like = (np.zeros(dim, dtype=np.dtype(dtype)), np.float64(0.0))
    restored = agreed_restore_latest(
        checkpoint_manager, like, mesh, "checkpoint restore (latest carry)"
    )
    if restored is None:
        return None
    (coef_h, loss_h), epoch = restored
    return coef_h, int(epoch), float(loss_h)


def _run_chunked(
    trainer,
    place,
    dim: int,
    dt,
    learning_rate: float,
    reg_l2: float,
    reg_l1: float,
    tol: float,
    max_iter: int,
    mesh: DeviceMesh,
    checkpoint_manager=None,
    checkpoint_interval: int = 0,
    resume: bool = False,
    listeners=(),
) -> np.ndarray:
    """Drive a carry-style trainer in dispatches that follow its table's
    placement, or the boundaries the caller asked for.

    ``place(first, last)`` starts the placement of the trainer's data
    args for steps ``[first, last)`` (:func:`_follow_windows`) and gives
    its rounds: ``(data_args, epoch the loop can run to)``.

    - No checkpoint manager and no listeners: after each round, if a
      further whole window has landed on every shard, the trainer is
      dispatched from the carry ON THE DEVICE up to the steps the landed
      windows allow, and runs while the next round is gathered and sent;
      the last round's dispatch runs to ``max_iter``. The carry is read
      back once, at the end: the loop's own condition stops every chunk
      whose entering loss is at or under ``tol``, so the result is bit
      for bit the one-dispatch fit's. A table of one round is one
      dispatch. ``trainer.loop`` spans all of it, the rounds' spans
      (``hostdata.stage_wait``, ``hostdata.shuffle``,
      ``mesh.shard_batch``) inside it.
    - With a manager or listeners the host takes the carry at every
      boundary, so the placement completes first; then each dispatch
      runs K epochs (the manager's interval; all of them without one)
      and the carry ``(coef, loss)`` is snapshotted at its epoch.
      Failure loses at most one chunk; ``resume=True`` restores the
      carry and re-enters the same executable, so the resumed trajectory
      is exactly the uninterrupted one (reference contract:
      ``Checkpoints.java:43-211`` exactly-once feedback logging → here,
      bit-exact carry replay). ``listeners`` fire at chunk boundaries
      (epoch granularity requires the host loop in ``iterate``; the
      device loop surfaces only chunk boundaries to the host).

    ``metrics.group("trainer")`` counts ``steps`` (run),
    ``pipelined_steps`` (of them, dispatched before the placement's last
    round was sent), ``mesh_devices`` (the data axis's size, added once a
    fit: over the fits, the workers of a fit) and ``psum_bytes`` (what a
    device hands a step's all-reduce, the gradient and the two sums, over
    the steps run; 0 on a mesh of one, which reduces nothing).
    """
    from flinkml_tpu.iteration.checkpoint import begin_resume

    resume_epoch = begin_resume(checkpoint_manager, resume, mesh.mesh.size)
    # The start carry and the hyper-parameters are host values of the
    # loop's own dtypes: the carry reaches the mesh in ONE ``device_put``,
    # the scalars as operands of the dispatches the fit makes anyway, and
    # no device operation of their own comes before the loop.
    coef = np.zeros(dim, dtype=dt)
    epoch = 0
    cur_loss = float("inf")
    if resume_epoch is not None:
        coef_h, epoch, cur_loss = _restore_carry(
            checkpoint_manager, dim, dt, mesh
        )
        coef = np.asarray(coef_h, dtype=dt)

    hy = tuple(np.asarray(v, dtype=dt)
               for v in (learning_rate, reg_l2, reg_l1, tol))
    first = epoch
    rounds = place(first, max_iter)
    at_host = checkpoint_manager is not None or bool(listeners)
    if at_host:
        data_args = _placed(rounds)
        chunk = (checkpoint_interval if checkpoint_manager is not None
                 and checkpoint_interval > 0 else max_iter)
        rounds = ((data_args, min(end, max_iter))
                  for end in range(first + chunk, max_iter + chunk, chunk))
    # On the mesh as the trainer returns it, so that a chunk entered from
    # the chunk before is the program the first one compiled (a host
    # operand and a returned carry are two signatures to ``jax.jit``).
    carry = jax.device_put(
        (coef, np.asarray(epoch, np.int32), np.asarray(cur_loss, dt)),
        mesh.replicated_sharding())
    # Steps [first, before) went out ahead of the last dispatch.
    before = sent = first
    with span("trainer.loop"):
        for data_args, ready in rounds:
            if ready <= sent or not cur_loss > tol:
                continue
            carry = trainer(*carry, *data_args, *hy, np.int32(ready))
            before, sent = sent, ready
            if at_host:
                coef_host = np.asarray(carry[0])
                epoch, cur_loss = int(carry[1]), float(carry[2])
                if checkpoint_manager is not None:
                    checkpoint_manager.save(
                        (coef_host, np.float64(cur_loss)), epoch
                    )
                for listener in listeners:
                    listener.on_epoch_watermark_incremented(epoch - 1, coef_host)
        # The one wait of a pipelined fit: every chunk has run, on every
        # device, before the span closes.
        epoch = int(jax.block_until_ready(carry)[1])
    counts = metrics.group("trainer")
    counts.counter("steps", float(epoch - first))
    counts.counter("pipelined_steps",
                   0.0 if at_host else float(min(epoch, before) - first))
    workers = mesh.axis_size()
    counts.counter("mesh_devices", float(workers))
    counts.counter("psum_bytes", 0.0 if workers == 1 else float(
        (epoch - first)
        * (coef.size * coef.itemsize + 2 * jnp.dtype(_acc_dt(dt)).itemsize)))
    with span("trainer.readback"):
        result = np.asarray(carry[0])
        if checkpoint_manager is not None:
            # Drain any in-flight async write so a failed final snapshot
            # surfaces here, not silently at interpreter exit.
            checkpoint_manager.wait()
        for listener in listeners:
            listener.on_iteration_terminated(result)
    return result


def _place_shuffled(x, y, w, mesh: DeviceMesh, seed: int, dtype,
                    reach_rows: Optional[int] = None):
    """The training table on its way to the mesh in the row order
    ``seed`` fixes, padded with zero rows (weight 0) to the mesh: the
    rounds of ``((xd, yd, wd), complete local rows)`` of ONE lockstep
    placement of its columns (:meth:`DeviceMesh.stage_rows`: gathered on
    the pool's threads, cast in the gather, never copied whole on the
    host; ``dtype`` None: each array's own). Run to its last round
    (:func:`_placed`) the three arrays are exactly what
    ``shard_batch(pad(a.astype(dtype)[perm]))`` places, on the local
    rows below ``reach_rows`` (None: all), zero above. The permutation
    is computed whole, here, before the first round is asked for. ``w``
    None is unit weights: made on the device
    (:meth:`DeviceMesh.shard_ones`), no host array at all."""
    with span("hostdata.shuffle"), span("hostdata.permute"):
        perm = np.random.default_rng(seed).permutation(x.shape[0])
    _count_unit_weights(w)
    columns = [(x, perm, dtype), (y, perm, dtype)]
    if w is not None:
        return mesh.stage_rows(columns + [(w, perm, dtype)], reach_rows)
    ones = _unit_weights(x.shape[0], mesh, dtype)
    return ((arrays + (ones,), complete)
            for arrays, complete in mesh.stage_rows(columns, reach_rows))


def _dense_placement(x, y, w, mesh: DeviceMesh, seed: int, dtype,
                     n_local: int, local_bs: int, kept):
    """What :func:`_run_chunked` follows for a dense table (the binomial
    and the softmax fits place the same three arrays): the rounds of
    :func:`_place_shuffled` as far as the steps reach, found again or
    kept with the table where ``kept`` names one (:func:`_find_or_keep`)."""
    place = _follow_windows(
        functools.partial(_place_shuffled, x, y, w, mesh, seed, dtype),
        n_local, local_bs)
    if kept is None:
        return place
    dt = _placed_dtype(x, dtype)
    rows = mesh.axis_size() * n_local
    # ``dtype`` None places every column at its own width.
    return _find_or_keep(
        kept("linear_rows_on_mesh", mesh.mesh,
             "own" if dtype is None else dt.name, seed), place,
        [(n_local, local_bs)],
        rows * (int(np.prod(x.shape[1:])) + 2) * dt.itemsize, mesh)


def _placed_dtype(x, dtype):
    """The dtype the device holds ``x`` at: ``dtype`` (None: ``x``'s
    own) as ``device_put`` narrows it where x64 is off."""
    return jnp.dtype(jax.dtypes.canonicalize_dtype(
        dtype if dtype is not None else x.dtype))


def _count_unit_weights(w) -> None:
    """``hostdata.unit_weights_on_device``: fits that had no weight
    column, whose weights the device made."""
    if w is None:
        metrics.group("hostdata").counter("unit_weights_on_device")


def _unit_weights(n: int, mesh: DeviceMesh, dtype):
    """The weights of ``n`` rows with no weight column (float64 where
    ``dtype`` names no width, as ``labeled_data``'s ones were), made on
    the device."""
    return mesh.shard_ones(n, dtype if dtype is not None else np.float64)


def train_linear_model(
    x: np.ndarray,
    y: np.ndarray,
    w: np.ndarray,
    loss: str,
    mesh: DeviceMesh,
    max_iter: int,
    learning_rate: float,
    global_batch_size: int,
    reg: float,
    elastic_net: float,
    tol: float,
    seed: int,
    dtype=None,
    checkpoint_manager=None,
    checkpoint_interval: int = 0,
    resume: bool = False,
    listeners=(),
    sharding_plan=None,
    precision=None,
    kept=None,
) -> np.ndarray:
    """Dense distributed training; returns the coefficient on host.

    ``y`` and ``w`` are 1-D columns of any numeric dtype, cast to the
    training dtype on their way to the device; ``w`` None is a weight of
    1.0 a row (see :func:`_place_shuffled`).

    ``reg``/``elastic_net`` follow the sklearn/Spark convention:
    l1 = reg * elastic_net, l2 = reg * (1 - elastic_net).

    With ``checkpoint_manager`` + ``checkpoint_interval`` K, training runs
    in K-epoch device dispatches with a carry snapshot after each — the
    fast whole-loop-on-device path IS the fault-tolerant path (see
    :func:`_run_chunked`). ``resume=True`` continues exactly from the
    latest snapshot.

    ``sharding_plan`` (a :class:`~flinkml_tpu.sharding.plan.
    ShardingPlan`) routes the fit through the plan-sharded trainer
    (:func:`flinkml_tpu.sharding.apply.train_linear_plan`): parameters
    and optimizer state shard per the plan (FSDP-style), batches along
    the plan's batch axes, checkpoints carry plan-derived layout tags.
    The plan path trains with momentum SGD over the same seeded row
    order — convergence-equivalent to (not bit-identical with) the
    replicated trainer. A mesh lacking the plan's axes is re-shaped
    over the same devices via :meth:`DeviceMesh.for_plan`.

    ``precision`` (a :class:`~flinkml_tpu.precision.PrecisionPolicy`,
    preset name, or policy JSON dict) declares the mixed-precision
    contract and routes the fit through the policy-gated plan trainer
    (under the ``replicated`` plan when no ``sharding_plan`` is given):
    the step's jaxpr is validated against the policy BEFORE any compile
    by the FML6xx precision-flow pass — see
    ``docs/development/precision.md``.

    ``kept`` (:func:`table_placements`; None for a caller with arrays
    and no table) is where the replicated fit keeps its placement WITH
    its table and finds it again (:func:`_find_or_keep`); the plan and
    precision paths keep nothing.
    """
    if loss not in _LOSS_KEYS:
        raise ValueError(f"loss must be one of {_LOSS_KEYS}, got {loss!r}")
    n = x.shape[0]
    if n == 0:
        raise ValueError("training table is empty")
    if precision is not None and sharding_plan is None:
        # The policy-gated step lives on the plan path; REPLICATED is
        # the plan-shaped spelling of "no sharding".
        from flinkml_tpu.sharding.plan import REPLICATED

        sharding_plan = REPLICATED
    if sharding_plan is not None:
        from flinkml_tpu.sharding.apply import train_linear_plan

        if listeners:
            raise ValueError(
                "listeners are not supported on the plan-sharded path"
            )
        if any(a not in mesh.mesh.shape
               for a in sharding_plan.required_axes()):
            mesh = DeviceMesh.for_plan(
                sharding_plan,
                devices=list(mesh.mesh.devices.reshape(-1)),
            )
        perm = np.random.default_rng(seed).permutation(n)
        w = np.ones(n) if w is None else w[perm]
        return train_linear_plan(
            x[perm], y[perm], w, sharding_plan, mesh, loss=loss,
            max_iter=max_iter, learning_rate=learning_rate,
            global_batch_size=global_batch_size, reg=reg,
            elastic_net=elastic_net, tol=tol, dtype=dtype,
            precision=precision,
            checkpoint_manager=checkpoint_manager,
            checkpoint_interval=checkpoint_interval, resume=resume,
        )
    p_size = mesh.axis_size()
    n_local = -(-n // p_size)
    local_bs = align_local_bs(global_batch_size, p_size, n_local)
    from flinkml_tpu.kernels import _mosaic, dense_step

    dt = _placed_dtype(x, dtype)
    # What the step will read off its operands (:func:`_rows_in_fast_
    # memory`), known here already: what tracing the kernel imports
    # loads beside the permutation, the gather and the upload.
    fused = dense_step.unsupported_reason(
        dt, n_local, local_bs, x.shape[1]) is None
    if fused:
        _mosaic.import_beside_host_work()
    trainer = _dense_trainer(mesh.mesh, loss, local_bs, DeviceMesh.DATA_AXIS)
    place = _dense_placement(x, y, w, mesh, seed, dtype, n_local, local_bs, kept)
    coef = _run_chunked(
        trainer, place, x.shape[1], dt,
        learning_rate, reg * (1.0 - elastic_net), reg * elastic_net,
        tol, max_iter, mesh,
        checkpoint_manager=checkpoint_manager,
        checkpoint_interval=checkpoint_interval,
        resume=resume, listeners=listeners,
    )
    # Counted at the loop, as ``fused_block_fits`` is: a fit that finds
    # its placement kept runs the same dispatches.
    metrics.group("trainer").counter("fused_dense_fits", float(fused))
    return coef


def prepare_sparse_buckets(
    indptr, indices, values, dim: int, y, w, mesh: DeviceMesh,
    global_batch_size: int, max_buckets: int = 4, dtype=np.float32,
    seed: Optional[int] = None, kept=None,
) -> Tuple[Tuple, Tuple[int, ...], Tuple]:
    """Pack CSR data for the bucketed trainer, and say how it is
    shuffled, padded and sharded onto the mesh.

    Returns ``(place, local_bss, slot_plan)``: the placement
    :func:`_run_chunked` follows, each bucket's per-device window size
    (proportional share of ``global_batch_size``, ≥ 1), and the plan the
    step follows. The single source of the batching policy — the
    benchmark measures exactly what the product trains with.

    ``place(first, last)`` starts the placement for steps ``[first,
    last)`` and gives its rounds, ``(data_args, epoch the loop can run
    to)``; ``data_args`` are the flat per-bucket sharded arrays (indices,
    values, y, w per bucket; under a plan the blocks' starts after
    them). A bucket's columns go up in lockstep through
    :meth:`DeviceMesh.stage_rows`, as far as the steps' windows of THAT
    bucket reach (:func:`_reach_rows` with its own rows and window).
    Rows of one width are one bucket, whose rounds the loop follows.
    Several ragged buckets (``pack_ell_buckets``): a step reads a window
    of every bucket, so all but the last land whole and the loop
    follows the last one's rounds; lockstep across buckets of different
    widths would cost more code than the buckets before the last hide.

    ``slot_plan`` is one observation of the cells, made here in every
    fit (``ops.sparse.slot_block_plan``, inside ``hostdata.sparse_pack``):
    where every row has one width, ELL slot ``j`` of every row is the
    row's ``j``-th cell, and a slot whose columns all lie in one short
    range (a field with a range of columns of its own) is *blocked*.
    The plan holds each blocked slot's block length and keys the program
    (:func:`_sparse_trainer_bucketed`); where the blocks start is the
    last of ``data_args``, an operand. Such a table with cells missing
    has ragged rows: it is laid one field a slot first
    (``ops.sparse.align_ragged_rows``, in place of the buckets' fill)
    and planned the same way. The cells of a one-width table are placed
    as they are. A table with no blocked slot (rows hashed over all of
    ``dim``, text rows in several buckets) has the empty plan ``()`` and
    nothing after its buckets' arrays. ``hostdata.sparse.blocked_slots``
    and ``.blocked_cells`` count what the plan covers.

    ``seed`` shuffles rows *within* each bucket (bucket membership depends
    only on nnz, so this is the reference's partition shuffle applied
    post-bucketing — no re-gather of the full CSR needed). Rows of one
    width are one bucket, so their order is
    ``default_rng(seed).permutation(rows)``, the dense fit's, and the
    bucket's rows are the table's: no row ids are made or gathered. The
    orders are computed whole when ``place`` is called, before the first
    round. The labels ``y`` (any numeric dtype, as the table holds them)
    and a weight column ``w`` go in the same rounds, cast to ``dtype`` in
    the gather; ``w`` None is unit weights, made on the device
    (:meth:`DeviceMesh.shard_ones`), as in :func:`_place_shuffled`.

    ``kept`` (:func:`table_placements`; None for a caller with arrays
    and no table): the placement is kept WITH the table once its last
    round has landed, with ``local_bss``, the plan and each bucket's
    reach, under a key of the mesh, ``dtype``, ``seed``,
    ``global_batch_size`` and ``max_buckets`` (the buckets and their
    windows follow from both). A table that holds one answers from it:
    no bucket is chosen, no plan read, and ``place`` on steps it covers
    is one round of the kept arrays (:func:`_find_or_keep`); steps past
    its reach pack and place as a table without one does.
    """
    p_size = mesh.axis_size()
    slot = found = None
    if kept is not None:
        slot = kept("linear_cells_on_mesh", mesh.mesh, np.dtype(dtype).name,
                    seed, int(global_batch_size), int(max_buckets))
        found = slot.find()
    if found is not None:
        local_bss, slot_plan, rows = found.plan

        def place(first: int, last: int):
            # The kept placement falls short of these steps.
            return _pack_sparse_buckets(
                indptr, indices, values, dim, y, w, mesh, global_batch_size,
                max_buckets, dtype, seed)[0](first, last)

        nbytes = sum(a.nbytes for a in found.arrays)
    else:
        place, local_bss, slot_plan, rows, nbytes = _pack_sparse_buckets(
            indptr, indices, values, dim, y, w, mesh, global_batch_size,
            max_buckets, dtype, seed)
    place = _find_or_keep(slot, place, list(zip(rows, local_bss)), nbytes,
                          mesh, (local_bss, slot_plan, rows))
    return place, local_bss, slot_plan


def _pack_sparse_buckets(indptr, indices, values, dim: int, y, w,
                         mesh: DeviceMesh, global_batch_size: int,
                         max_buckets: int, dtype, seed: Optional[int]):
    """:func:`prepare_sparse_buckets` for a table with no kept placement:
    the bucket choice, the plan and the placement it documents, as
    ``(place, local_bss, slot_plan, local rows a bucket, bytes the
    placement puts on the mesh)``."""
    indptr = np.asarray(indptr, dtype=np.int64)
    n = indptr.size - 1
    y = np.asarray(y)
    p_size = mesh.axis_size()
    with span("hostdata.sparse_pack"):
        # Bucket choice and ELL fill. Rows of one width (hashed
        # categorical data) are one bucket of two views: nothing is
        # filled, and the span is the look at ``indptr`` and the plan.
        # (The block products move float32 unrounded, and no other
        # width: any other training dtype has no plan.)
        with gather_pool() as pool:
            block, slot_plan, starts, blocked_cells = planned_block(
                indptr, indices, values, dim, dtype,
                math.ceil(global_batch_size / p_size), pool,
                plan=np.dtype(dtype) == np.float32)
        if block is None:
            # Ragged rows that keep to no fields (or to fields too wide
            # to block): padded buckets, as before there were plans.
            buckets, row_ids = pack_ell_buckets(
                indptr, indices, values, dim, max_buckets=max_buckets,
                dtype=dtype)
        else:
            # One block whose slot j holds every row's j-th cell (or
            # its j-th field's).
            buckets, row_ids = [block], [None]
    counts = metrics.group("hostdata.sparse")
    counts.counter("cells", float(indptr[-1]))
    counts.counter("blocked_slots",
                   float(sum(length is not None for length in slot_plan)))
    counts.counter("blocked_cells", blocked_cells)
    counts.counter("padded_cells",
                   float(sum(b["indices"].size for b in buckets)))
    counts.counter("buckets", float(len(buckets)))
    local_bss = tuple(
        min(max(1, math.ceil(global_batch_size * b["indices"].shape[0]
                             / (n * p_size))),
            -(-b["indices"].shape[0] // p_size))
        for b in buckets)

    def place(first: int, last: int):
        _count_unit_weights(w)
        rng = np.random.default_rng(seed) if seed is not None else None
        placements = []
        for bucket, rows, local_bs in zip(buckets, row_ids, local_bss):
            bi, bv = bucket["indices"], bucket["values"]
            n_bucket = bi.shape[0]
            with span("hostdata.shuffle"), span("hostdata.permute"):
                order = (rng.permutation(n_bucket) if rng is not None
                         else np.arange(n_bucket))
                # The table's rows this bucket's positions hold: where
                # every row has one width the bucket's rows ARE the
                # table's.
                picked = order if rows is None else rows[order]
            # One pass, as the dense fit's: the seeded order gathered
            # round by round on its way to the device, no permuted copy
            # of the block on the host (DeviceMesh.stage_rows places
            # exactly shard_batch(pad(block[order])) below the reach).
            columns = [(bi, order, np.int32), (bv, order, dtype),
                       (y, picked, dtype)]
            if w is not None:
                columns.append((w, picked, dtype))
            follow = _follow_windows(
                functools.partial(mesh.stage_rows, columns),
                -(-n_bucket // p_size), local_bs)
            placements.append((follow(first, last), n_bucket))

        def rounds():
            # A step reads a window of every bucket: all but the last
            # bucket land whole (as far as the steps reach), and the
            # loop follows the last one's rounds.
            units = [() if w is not None
                     else (_unit_weights(n_bucket, mesh, dtype),)
                     for _, n_bucket in placements]
            head = ()
            for (placement, _), unit in zip(placements[:-1], units):
                head += _placed(placement) + unit
            tail = units[-1]
            if slot_plan:
                # Every device's shard the same [width] starts (a few
                # bytes: under no span, as the unit weights are).
                tail += (jax.device_put(
                    np.tile(starts, p_size), mesh.data_sharding()),)
            for arrays, ready in placements[-1][0]:
                yield head + arrays + tail, ready

        return rounds()

    rows = tuple(-(-b["indices"].shape[0] // p_size) for b in buckets)
    width = np.dtype(dtype).itemsize
    # A bucket's cells (int32 indices, values) and its labels and weights.
    nbytes = sum(p_size * r * (b["indices"].shape[1] * (4 + width) + 2 * width)
                 for r, b in zip(rows, buckets))
    return place, local_bss, slot_plan, rows, nbytes


def train_linear_model_sparse_csr(
    indptr: np.ndarray,
    indices: np.ndarray,
    values: np.ndarray,
    dim: int,
    y: np.ndarray,
    w: np.ndarray,
    loss: str,
    mesh: DeviceMesh,
    max_iter: int,
    learning_rate: float,
    global_batch_size: int,
    reg: float,
    elastic_net: float,
    tol: float,
    seed: int,
    max_buckets: int = 4,
    dtype=np.float32,
    checkpoint_manager=None,
    checkpoint_interval: int = 0,
    resume: bool = False,
    listeners=(),
    kept=None,
) -> np.ndarray:
    """Skew-proof sparse training from host CSR arrays.

    Rows go into nnz-bucketed ELL blocks (``ops.sparse.pack_ell_buckets``)
    and not one block padded to the dataset's max nnz (pathological under
    skewed nnz): total padded cells ≈ total nnz, so HBM cost scales with
    the data, not with the worst row. Each step takes a proportional
    window from every bucket (stratified batch); with batch ≥ n this is
    exactly the full-dataset gradient. ``y`` and ``w`` as in
    :func:`prepare_sparse_buckets` (``w`` None: unit weights; ``kept``:
    where the placement stays with its table).
    """
    if loss not in _LOSS_KEYS:
        raise ValueError(f"loss must be one of {_LOSS_KEYS}, got {loss!r}")
    n = np.asarray(indptr).size - 1
    if n == 0:
        raise ValueError("training table is empty")
    if np.dtype(dtype) == np.float32:
        # A plan's step may hold the block kernels (a TPU's): what
        # tracing them imports loads beside the pack and the permutation.
        from flinkml_tpu.kernels import _mosaic

        _mosaic.import_beside_host_work()
    place, local_bss, slot_plan = prepare_sparse_buckets(
        indptr, indices, values, dim, y, w, mesh, global_batch_size,
        max_buckets=max_buckets, dtype=dtype, seed=seed, kept=kept,
    )
    trainer = _sparse_trainer_bucketed(
        mesh.mesh, loss, tuple(local_bss), DeviceMesh.DATA_AXIS, int(dim),
        slot_plan)
    coef = _run_chunked(
        trainer, place, int(dim), jnp.dtype(dtype),
        learning_rate, reg * (1.0 - elastic_net), reg * elastic_net,
        tol, max_iter, mesh,
        checkpoint_manager=checkpoint_manager,
        checkpoint_interval=checkpoint_interval,
        resume=resume, listeners=listeners,
    )
    # Counted at the loop, whose dispatches carry the kernels or do not:
    # a fit that finds its placement kept packs nothing and still counts.
    metrics.group("trainer").counter("fused_block_fits", float(
        _blocks_in_fast_memory(dtype, local_bss[0], slot_plan)))
    return coef


def make_softmax_step(num_classes: int, local_bs: int, axis: str):
    """Multinomial (softmax) step: logits on the MXU, cross-entropy on
    the VPU, gradient ``(p - onehot)ᵀ·x`` back on the MXU. The model is a
    ``[k, d]`` matrix; same update rule as the binomial trainer
    (``coef -= lr/weightSum · grad``)."""

    def step(coef, epoch, xl, yl, wl, learning_rate, reg_l2, reg_l1):
        xb = _window(xl, epoch, local_bs)
        yb = _window(yl, epoch, local_bs)
        wb = _window(wl, epoch, local_bs)
        acc = _acc_dt(xb.dtype)
        logits = xb @ coef.T                             # [bs, k]
        logp = jax.nn.log_softmax(logits, axis=-1)
        onehot = jax.nn.one_hot(
            yb.astype(jnp.int32), num_classes, dtype=xb.dtype
        )
        per_ex = -jnp.sum(onehot * logp, axis=-1) * wb
        mult = (jnp.exp(logp) - onehot) * wb[:, None]    # [bs, k]
        grad_l = mult.T @ xb                             # [k, d]
        grad = jax.lax.psum(grad_l, axis)
        loss_sum = jax.lax.psum(jnp.sum(per_ex.astype(acc)), axis)
        wsum = jax.lax.psum(jnp.sum(wb.astype(acc)), axis)
        grad = grad + 2.0 * reg_l2 * coef
        loss_sum = loss_sum + reg_l2 * jnp.sum(jnp.square(coef.astype(acc)))
        step_size = learning_rate.astype(acc) / wsum
        new_coef = _soft_threshold(
            coef - step_size.astype(coef.dtype) * grad,
            step_size.astype(coef.dtype) * reg_l1,
        )
        return new_coef, (loss_sum / wsum).astype(coef.dtype)

    return step


@functools.lru_cache(maxsize=128)
def _softmax_trainer(mesh, num_classes: int, local_bs: int, axis: str):
    """The softmax whole-loop trainer (:func:`_whole_loop`)."""
    return _whole_loop(
        mesh, make_softmax_step(num_classes, local_bs, axis), 3, axis,
        "lr_softmax_loop")


def train_softmax_model(
    x: np.ndarray,
    y: np.ndarray,
    w: np.ndarray,
    num_classes: int,
    mesh: DeviceMesh,
    max_iter: int,
    learning_rate: float,
    global_batch_size: int,
    reg: float,
    elastic_net: float,
    tol: float,
    seed: int,
    dtype=None,
    checkpoint_manager=None,
    checkpoint_interval: int = 0,
    resume: bool = False,
    listeners=(),
    kept=None,
) -> np.ndarray:
    """Multinomial logistic regression: returns coefficient ``[k, d]``.

    Same distributed machinery as :func:`train_linear_model` (windowed
    batches, psum, proximal elastic-net, chunked checkpointing, the
    placement ``kept`` with the table); the loss is weighted softmax
    cross-entropy over integer labels ``0..k-1``.
    """
    n = x.shape[0]
    if n == 0:
        raise ValueError("training table is empty")
    p_size = mesh.axis_size()
    n_local = -(-n // p_size)
    local_bs = align_local_bs(global_batch_size, p_size, n_local)
    trainer = _softmax_trainer(
        mesh.mesh, int(num_classes), local_bs, DeviceMesh.DATA_AXIS
    )
    # Labels and weights at the features' width, as the step expects.
    dtype = dtype if dtype is not None else x.dtype
    place = _dense_placement(x, y, w, mesh, seed, dtype, n_local, local_bs, kept)
    return _run_chunked(
        trainer, place, (int(num_classes), x.shape[1]),
        _placed_dtype(x, dtype),
        learning_rate, reg * (1.0 - elastic_net), reg * elastic_net,
        tol, max_iter, mesh,
        checkpoint_manager=checkpoint_manager,
        checkpoint_interval=checkpoint_interval,
        resume=resume, listeners=listeners,
    )


def _run_multiprocess_stream_epochs(
    cache, plan, place, stepper, dim, hy, dt, criterion,
    checkpoint_manager, checkpoint_interval, listeners, prefetch_depth,
    mesh, coef, epoch, cur_loss, after_first_epoch=None,
):
    """The shared multi-process epoch driver for the dense and sparse
    stream trainers: agreed-schedule replay through the prefetching
    feed, bounded in-flight dispatch, watermark listeners, rank-0 +
    barrier checkpoint commits, and the termination epilogue (async
    checkpoint ``wait`` — which also surfaces a failed final write —
    plus ``on_iteration_terminated``). ONE definition so the two paths
    cannot drift (they already had once: the sparse copy dropped the
    epilogue)."""
    from flinkml_tpu.iteration.checkpoint import save_replicated
    from flinkml_tpu.iteration.datacache import PrefetchingDeviceFeed
    from flinkml_tpu.parallel.dispatch import DispatchGuard

    guard = DispatchGuard()

    def run_epoch(coef):
        loss_acc = jnp.zeros((), dt)
        wsum_acc = jnp.zeros((), dt)
        feed = PrefetchingDeviceFeed(
            plan.epoch_batches(cache.reader(), lambda: _DUMMY_BATCH),
            place=place,
            depth=prefetch_depth,
        )
        try:
            for tensors in feed:
                if coef is None:
                    coef = jnp.zeros(dim, dt)
                coef, ls, ws = stepper(coef, *tensors, *hy)
                loss_acc = loss_acc + ls
                wsum_acc = wsum_acc + ws
                coef = guard.after_dispatch(coef)
        finally:
            feed.close()
        coef = guard.flush(coef)
        return coef, float(loss_acc) / float(wsum_acc)

    while not (epoch > 0 and criterion.should_terminate(epoch - 1, cur_loss)):
        coef, cur_loss = run_epoch(coef)
        epoch += 1
        if after_first_epoch is not None:
            after_first_epoch()
        coef_host = np.asarray(coef)
        for listener in listeners:
            listener.on_epoch_watermark_incremented(epoch - 1, coef_host)
        terminated = criterion.should_terminate(epoch - 1, cur_loss)
        if checkpoint_manager is not None and (
            terminated
            or (checkpoint_interval > 0 and epoch % checkpoint_interval == 0)
        ):
            save_replicated(
                checkpoint_manager,
                (coef_host, np.float64(cur_loss)),
                epoch,
                mesh,
            )

    result = np.asarray(coef)
    if checkpoint_manager is not None:
        checkpoint_manager.wait()  # surface a failed final async write
    for listener in listeners:
        listener.on_iteration_terminated(result)
    return result


def _train_linear_sparse_stream_multiprocess(
    batches,
    loss: str,
    mesh: DeviceMesh,
    max_iter: int,
    learning_rate: float,
    reg: float,
    elastic_net: float,
    tol: float,
    cache_dir: Optional[str],
    memory_budget_bytes: Optional[int],
    checkpoint_manager,
    checkpoint_interval: int,
    resume: bool,
    listeners,
    prefetch_depth: int,
    dtype,
    validate,
    sparse_dim: int,
) -> np.ndarray:
    """Multi-process body of the sparse-native stream (the pod-scale
    Criteo path): each process feeds its OWN partition of flat CSR
    batches. SPMD invariants mirror
    :func:`_train_linear_stream_multiprocess`, with ONE extra agreed
    quantity — a single global ELL width (the max quantized per-batch
    width across every rank's stream), so every collective dispatch has
    one fixed ``[height, width]`` shape. Ingest failures, including
    dim-mismatched or ragged CSR components, ride the held-error
    rendezvous; short ranks feed zero-weight dummy blocks (exact
    no-ops). O(nnz) cache and HBM cost at any ``dim``, per rank."""
    from flinkml_tpu.iteration.checkpoint import begin_resume
    from flinkml_tpu.iteration.datacache import DataCache, DataCacheWriter
    from flinkml_tpu.iteration.runtime import TerminateOnMaxIterOrTol
    from flinkml_tpu.iteration.stream_sync import (
        DeferredValidation,
        SyncedReplayPlan,
        agree_all_ok,
        agree_max,
        checked_ingest,
        pad_rows_to,
    )

    is_cache = isinstance(batches, DataCache)
    resume_epoch = begin_resume(checkpoint_manager, resume, mesh.mesh.size)

    p_size = mesh.axis_size()
    row_tile = p_size * 8
    axis = DeviceMesh.DATA_AXIS
    stepper = _sparse_stream_stepper(mesh.mesh, loss, axis, int(sparse_dim))
    l2 = reg * (1.0 - elastic_net)
    l1 = reg * elastic_net

    # -- pass 0: cache + local (rows, width) maxima; everything a
    # place-time raise could hit is validated HERE (a feed-thread raise
    # is rank-local mid-collective — the hang class).
    dv = DeferredValidation()
    local_max = [0, 0]  # rows, quantized width

    def check_and_stats(b):
        indptr = np.asarray(b["indptr"])[0]
        n = indptr.size - 1
        d = int(np.asarray(b["dim"]).reshape(-1)[0])
        if d != sparse_dim:
            raise ValueError(
                f"CSR stream batch has dim {d}, expected {sparse_dim}"
            )
        indices = np.asarray(b["indices"])[0]
        values = np.asarray(b["values"])[0]
        if indices.shape != values.shape or indices.size != int(indptr[-1]):
            raise ValueError(
                "ragged CSR batch: indices/values/indptr disagree"
            )
        nnz = _check_csr_structure(indptr, indices, sparse_dim)
        y = np.asarray(b["y"])[0]
        w = (np.asarray(b["w"])[0] if "w" in b
             else np.ones(n, dtype=dtype))
        if y.shape[0] != n or w.shape[0] != n:
            raise ValueError("ragged CSR batch: y/w rows != indptr rows")
        if validate is not None:
            validate(b)
        if n == 0 or float(w.sum()) == 0.0:
            raise ValueError(
                "stream batch has zero total weight (empty batch or all "
                "weights 0); drop such batches before training"
            )
        local_max[0] = max(local_max[0], n)
        local_max[1] = max(
            local_max[1], _ell_width_for(np.max(nnz, initial=1))
        )

    if is_cache:
        cache = batches
        for _ in checked_ingest(
            cache.reader(), dv, check_and_stats, multi=True
        ):
            pass
    else:
        writer = DataCacheWriter(cache_dir, memory_budget_bytes)

        def checked_append(b):
            check_and_stats(b)
            writer.append({k: np.array(v) for k, v in b.items()})

        for _ in checked_ingest(batches, dv, checked_append, multi=True):
            pass
        cache = writer.finish()

    dv.rendezvous(mesh, "sparse stream ingest validation")
    # Agree the feature dimension itself (the dense path's
    # agree_feature_dim role): per-rank validation above only checks
    # batches against the RANK-LOCAL sparse_dim — two ranks fed
    # partitions from different feature spaces would otherwise compile
    # different [dim] coefficient shapes and diverge inside the
    # collectives (the exact hang class pass 0 exists to prevent).
    agree_all_ok(
        agree_max(int(sparse_dim), mesh) == int(sparse_dim), mesh,
        "sparse stream feature-dimension agreement",
    )
    steps = agree_max(cache.num_batches, mesh)
    if steps == 0:
        raise ValueError("training stream is empty on every process")
    height = agree_max(
        -(-max(local_max[0], 1) // row_tile) * row_tile, mesh
    )
    width = agree_max(max(local_max[1], 1), mesh)
    plan = SyncedReplayPlan(
        global_steps=steps, local_height=height, mesh=mesh
    )

    def place(batch):
        if "_dummy" in batch:
            bi = np.zeros((height, width), np.int32)
            bv = np.zeros((height, width), dtype)
            y = np.zeros(height, dtype)
            w = np.zeros(height, dtype)
        else:
            indptr = np.asarray(batch["indptr"])[0]
            n = indptr.size - 1
            bi, bv = _pack_uniform_ell(
                indptr, np.asarray(batch["indices"])[0],
                np.asarray(batch["values"])[0], dtype, width=width,
            )
            bi = pad_rows_to(bi, height)
            bv = pad_rows_to(bv, height)
            y = pad_rows_to(
                np.asarray(batch["y"])[0].astype(dtype), height
            )
            w = pad_rows_to(
                (np.asarray(batch["w"])[0].astype(dtype)
                 if "w" in batch else np.ones(n, dtype=dtype)),
                height,
            )
        return (
            mesh.global_batch(bi), mesh.global_batch(bv),
            mesh.global_batch(y), mesh.global_batch(w),
        )

    dt = jnp.dtype(dtype)
    hy = (
        jnp.asarray(learning_rate, dt),
        jnp.asarray(l2, dt),
        jnp.asarray(l1, dt),
    )
    criterion = TerminateOnMaxIterOrTol(max_iter, tol)

    coef = None
    epoch = 0
    cur_loss = math.inf
    if resume_epoch is not None:
        restored = _restore_carry(checkpoint_manager, sparse_dim, dtype,
                                  mesh)
        if restored is not None:
            coef_h, epoch, cur_loss = restored
            coef = jnp.asarray(coef_h, dt)

    return _run_multiprocess_stream_epochs(
        cache, plan, place, stepper, int(sparse_dim), hy, dt, criterion,
        checkpoint_manager, checkpoint_interval, listeners, prefetch_depth,
        mesh, coef, epoch, cur_loss,
    )


def streamed_linear_fit(
    source,
    *,
    features_col: str,
    label_col: str,
    weight_col: Optional[str],
    label_check=None,
    **kwargs,
) -> np.ndarray:
    """Estimator-facing wrapper over :func:`train_linear_model_stream` —
    the one streamed dispatch for every linear estimator (LR binomial,
    LinearSVC, LinearRegression): accepts an iterable of batch Tables or
    a sealed DataCache carrying the given columns, applying
    ``label_check`` on either branch. ``kwargs`` pass straight through
    (loss, mesh, cache_dir, checkpoint_manager, ...).

    SparseVector feature columns route to the sparse-native stream
    (round 5): batches are cached and trained as CSR — O(nnz) cache and
    HBM cost at any ``dim`` — instead of densifying to ``[n, dim]``
    (ruinous at the Criteo profile: a 64-row batch at dim=1e6 would
    cache 256 MB). Multi-process meshes stream per-rank CSR partitions
    through the agreement layer with one extra agreed quantity (a
    global ELL width). A sealed DataCache
    whose batches carry ``indptr/indices/values/dim`` replays through
    the same sparse stream (this is also the resume route)."""
    from flinkml_tpu.iteration.datacache import DataCache
    from flinkml_tpu.models._data import (
        labeled_data,
        labeled_sparse_data,
        sparse_features,
    )

    if isinstance(source, DataCache):
        validate = None
        mem = source.mem_batches  # property: List[Batch]
        if mem:
            first = mem[0]  # no segment read for RAM-resident caches
        else:
            try:
                first = next(iter(source.reader()))
            except StopIteration:
                raise ValueError("training stream is empty") from None
        if "indptr" in first:  # sparse-native CSR cache
            if label_check is not None:
                def validate(batch):
                    label_check(np.asarray(batch["y"])[0])

            return train_linear_model_stream(
                source, columns=("x", "y", "w"), validate=validate,
                sparse_dim=int(np.asarray(first["dim"])[0, 0]), **kwargs,
            )
        if label_check is not None:
            def validate(batch):
                label_check(np.asarray(batch[label_col]))

        return train_linear_model_stream(
            source, columns=(features_col, label_col, weight_col),
            validate=validate, **kwargs,
        )

    import itertools

    it = iter(source)
    try:
        first_t = next(it)
    except StopIteration:
        raise ValueError("training stream is empty") from None
    tables = itertools.chain([first_t], it)

    from flinkml_tpu.table import SortedSparseColumn, Table

    if (
        isinstance(first_t, Table)
        and features_col in first_t.column_names
        and isinstance(first_t._raw_column(features_col), SortedSparseColumn)
    ):
        # Device-resident sorted-layout stream (DevicePrefetcher output):
        # train directly on the pack-time-sorted tables — no host
        # round-trip, no densify, no runtime sort.
        return train_linear_model_sorted_stream(
            tables, features_col, label_col, weight_col,
            label_check=label_check, **kwargs,
        )

    if sparse_features(first_t, features_col) is not None:
        indptr0, indices0, values0, dim0, y0, w0 = labeled_sparse_data(
            first_t, features_col, label_col, weight_col
        )

        def sparse_batches():
            for i, t in enumerate(tables):
                if i == 0:
                    indptr, indices, values, d, y, w = (
                        indptr0, indices0, values0, dim0, y0, w0
                    )
                else:
                    indptr, indices, values, d, y, w = labeled_sparse_data(
                        t, features_col, label_col, weight_col
                    )
                if d != dim0:
                    raise ValueError(
                        f"stream batch feature dimension {d} != first "
                        f"batch's {dim0}"
                    )
                if label_check is not None:
                    label_check(y)
                # Each array rides as one 2-D row: the cache's columnar
                # contract wants equal row counts per batch, and CSR
                # components have different lengths by nature.
                yield {
                    "indptr": np.asarray(indptr)[None, :],
                    "indices": np.asarray(indices)[None, :],
                    "values": np.asarray(values)[None, :],
                    "y": np.asarray(y)[None, :],
                    "w": np.asarray(w)[None, :],
                    "dim": np.asarray([[d]], np.int64),
                }

        return train_linear_model_stream(
            sparse_batches(), sparse_dim=int(dim0), **kwargs
        )

    def batches():
        for t in tables:
            x, y, w = labeled_data(t, features_col, label_col, weight_col)
            if label_check is not None:
                label_check(y)
            yield {"x": x, "y": y, "w": w}

    return train_linear_model_stream(batches(), **kwargs)


def dense_table_data(table, features_col: str, label_col: str,
                     weight_col: Optional[str], replicated: bool):
    """A dense trainer's columns and the ``dtype`` to hand it: ``(x,
    labels, w, dtype)``, ``labels`` the label column's
    :class:`~flinkml_tpu.models._data.LabelFacts` (the label checks
    answer from it; ``labels.values`` is the trainer's ``y``).

    The replicated trainers (:func:`train_linear_model` without a plan,
    :func:`train_softmax_model`) cast every column chunk by chunk on its
    way to the device, so they take each as the table has it
    (``_data.fit_columns``; ``w`` None without a weight column), and are
    told the width ``labeled_data``'s float64 copy gave every table
    (float32 on the device where x64 is off). The plan-sharded trainer
    computes on the host arrays and derives its own width from them: it
    keeps ``labeled_data``'s copies, and ``dtype`` is None."""
    from flinkml_tpu.models._data import LabelFacts, fit_columns, labeled_data

    if replicated:
        return (*fit_columns(table, features_col, label_col, weight_col),
                np.float64)
    x, y, w = labeled_data(table, features_col, label_col, weight_col)
    return x, LabelFacts(y), w, None


def train_linear_model_from_table(
    table,
    features_col: str,
    label_col: str,
    weight_col: Optional[str],
    label_check=None,
    sharding_plan=None,
    precision=None,
    **hyper,
) -> np.ndarray:
    """One fit dispatch for every linear estimator: SparseVector columns
    take the nnz-bucketed CSR trainer, everything else densifies into the
    dense trainer. ``label_check(labels)`` (optional) validates the
    label column's ``LabelFacts`` on either branch. ``hyper`` passes straight to the trainers (loss, mesh,
    max_iter, ...). ``sharding_plan`` routes the DENSE branch through
    the plan-sharded trainer (see :func:`train_linear_model`); the
    sparse trainer keeps its replicated ``[dim]`` model and refuses a
    plan loudly. ``precision`` (the FML6xx-gated mixed-precision
    policy) rides the same dense-only route and is refused just as
    loudly on the sparse branch. The replicated fits keep their
    placement with ``table`` (:func:`table_placements`)."""
    from flinkml_tpu.models._data import sparse_features, sparse_fit_columns

    kept = table_placements(table, features_col, label_col, weight_col)
    if sparse_features(table, features_col) is not None:
        if sharding_plan is not None:
            raise ValueError(
                "sharding_plan supports the dense path only; the sparse "
                "trainer keeps its replicated [dim] model (shard it via "
                "ROADMAP item 5's embedding-table path instead)"
            )
        if precision is not None:
            raise ValueError(
                "precision supports the dense path only; the sparse "
                "trainer's gather/segment-sum kernels are not yet "
                "policy-gated"
            )
        indptr, indices, values, dim, labels, w = sparse_fit_columns(
            table, features_col, label_col, weight_col
        )
        if label_check is not None:
            label_check(labels)
        return train_linear_model_sparse_csr(
            indptr, indices, values, dim, labels.values, w, kept=kept, **hyper
        )
    x, labels, w, dtype = dense_table_data(
        table, features_col, label_col, weight_col,
        replicated=sharding_plan is None and precision is None,
    )
    if x.shape[0] == 0:
        raise ValueError("training table is empty")
    if label_check is not None:
        label_check(labels)
    hyper.setdefault("dtype", dtype)
    return train_linear_model(x, labels.values, w, sharding_plan=sharding_plan,
                              precision=precision, kept=kept, **hyper)


# ---------------------------------------------------------------------------
# Streamed / out-of-core training (the load-bearing ReplayOperator path)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=128)
def _stream_stepper(mesh, loss: str, axis: str):
    """One global mini-batch SGD step for streamed training: the batch
    arrives sharded over ``axis``, the coefficient stays replicated.
    Returns unnormalized ``(loss_sum, wsum)`` so the host can accumulate a
    weighted epoch-mean loss across variable-size batches."""

    def per_device(coef, xb, yb, wb, learning_rate, reg_l2, reg_l1):
        acc = _acc_dt(xb.dtype)
        dot = xb @ coef
        mult, per_ex = _margin_grad(loss, dot, yb, wb)
        grad = jax.lax.psum(xb.T @ mult, axis) + 2.0 * reg_l2 * coef
        loss_sum = jax.lax.psum(jnp.sum(per_ex.astype(acc)), axis) + (
            reg_l2 * jnp.sum(jnp.square(coef.astype(acc)))
        )
        wsum = jax.lax.psum(jnp.sum(wb.astype(acc)), axis)
        step_size = learning_rate.astype(acc) / wsum
        new_coef = _soft_threshold(
            coef - step_size.astype(coef.dtype) * grad,
            step_size.astype(coef.dtype) * reg_l1,
        )
        return new_coef, loss_sum, wsum

    return jax.jit(
        jax.shard_map(
            per_device,
            mesh=mesh,
            in_specs=(P(), P(axis), P(axis), P(axis), P(), P(), P()),
            out_specs=(P(), P(), P()),
        )
    )


@functools.lru_cache(maxsize=64)
def _sparse_stream_stepper(mesh, loss: str, axis: str, dim: int):
    """Sparse sibling of :func:`_stream_stepper`: the batch arrives as a
    sharded padded-ELL block (indices/values), the dense ``[dim]``
    coefficient stays replicated. ELL matvec forward + one
    ``segment_sum`` gradient scatter."""

    def per_device(coef, ib, vb, yb, wb, learning_rate, reg_l2, reg_l1):
        acc = _acc_dt(vb.dtype)
        dot = ell_matvec(ib, vb, coef)
        mult, per_ex = _margin_grad(loss, dot, yb, wb)
        contrib = (vb * mult[:, None]).reshape(-1)
        grad = jax.lax.psum(
            jax.ops.segment_sum(contrib, ib.reshape(-1), num_segments=dim),
            axis,
        ) + 2.0 * reg_l2 * coef
        loss_sum = jax.lax.psum(jnp.sum(per_ex.astype(acc)), axis) + (
            reg_l2 * jnp.sum(jnp.square(coef.astype(acc)))
        )
        wsum = jax.lax.psum(jnp.sum(wb.astype(acc)), axis)
        step_size = learning_rate.astype(acc) / wsum
        new_coef = _soft_threshold(
            coef - step_size.astype(coef.dtype) * grad,
            step_size.astype(coef.dtype) * reg_l1,
        )
        return new_coef, loss_sum, wsum

    return jax.jit(
        jax.shard_map(
            per_device,
            mesh=mesh,
            in_specs=(P(), P(axis), P(axis), P(axis), P(axis), P(), P(),
                      P()),
            out_specs=(P(), P(), P()),
        )
    )


@functools.lru_cache(maxsize=64)
def _sorted_column_stepper(loss: str, dim: int):
    """Step factory for :func:`train_linear_model_sorted_stream`: one
    SGD step over a prefetched :class:`~flinkml_tpu.table
    .SortedSparseColumn` batch. Pure ``jax.jit`` — the column's global
    sort tables (``perm``/``segment_ids``) index the FULL flat cell
    block, which does not shard by rows, so the replicated single-
    program step is the correct shape here (psum-free).

    The forward is the ELL matvec over the padded-ELL block; the
    gradient scatter replays the pack-time sort —
    ``segment_sum(take(contrib, perm), segment_ids,
    indices_are_sorted=True)`` — so the step contains ZERO runtime
    sorts (the argsort already ran once on the prefetch worker
    thread). Row-bucket padding is neutralized in-jit: the weight
    column is masked by the traced ``n_valid`` row count (weight 0 ⇒
    exact zero contribution to grad/loss/wsum), so batch-size jitter
    inside a bucket never retraces."""

    def step(coef, ib, vb, perm, seg, yb, wb, n_valid, learning_rate,
             reg_l2, reg_l1):
        acc = _acc_dt(vb.dtype)
        yb = yb.astype(vb.dtype)
        wb = jnp.where(
            jnp.arange(wb.shape[0]) < n_valid,
            wb.astype(vb.dtype),
            jnp.zeros((), vb.dtype),
        )
        dot = ell_matvec(ib, vb, coef)
        mult, per_ex = _margin_grad(loss, dot, yb, wb)
        contrib = (vb * mult[:, None]).reshape(-1)
        scattered = jax.ops.segment_sum(
            jnp.take(contrib, perm), seg, num_segments=dim,
            indices_are_sorted=True,
        )
        # Without the barrier XLA folds the L2 term into the scatter's
        # init operand (each coefficient's sum would START from it);
        # the CSR stream's psum keeps it last. Same order, same bits.
        grad = jax.lax.optimization_barrier(scattered) + (
            2.0 * reg_l2 * coef
        )
        loss_sum = jnp.sum(per_ex.astype(acc)) + (
            reg_l2 * jnp.sum(jnp.square(coef.astype(acc)))
        )
        wsum = jnp.sum(wb.astype(acc))
        step_size = learning_rate.astype(acc) / wsum
        new_coef = _soft_threshold(
            coef - step_size.astype(coef.dtype) * grad,
            step_size.astype(coef.dtype) * reg_l1,
        )
        return new_coef, loss_sum, wsum

    return jax.jit(step)


def train_linear_model_sorted_stream(
    tables,
    features_col: str,
    label_col: str,
    weight_col: Optional[str] = None,
    *,
    loss: str,
    max_iter: int,
    learning_rate: float,
    reg: float,
    elastic_net: float,
    tol: float,
    mesh=None,
    label_check=None,
    listeners=(),
    dtype=np.float32,
    cache_dir=None,
    memory_budget_bytes=None,
    checkpoint_manager=None,
    checkpoint_interval: int = 0,
    resume: bool = False,
    prefetch_depth: int = 2,
    validate=None,
) -> np.ndarray:
    """Train a linear model from a stream of DEVICE-resident Tables
    whose feature column is a :class:`~flinkml_tpu.table
    .SortedSparseColumn` (the :class:`~flinkml_tpu.data.prefetch
    .DevicePrefetcher` output format): the sorted-by-design fast path —
    the fit never densifies to ``[n, dim]`` and never sorts at step
    time; the pack-time tables carry ``indices_are_sorted=True``
    straight into the gradient scatter.

    Epoch 0 trains batch-by-batch while collecting the device Tables
    into a list; later epochs replay that list — the batches are
    ALREADY in HBM (O(nnz) per batch), so the replay cache is the
    tables themselves and ``cache_dir`` / ``memory_budget_bytes`` /
    ``prefetch_depth`` are accepted for call-compatibility but unused.
    ``mesh`` likewise: the column's global sort tables index the full
    flat cell block and do not shard by rows, so the step is a
    replicated single-program jit (see :func:`_sorted_column_stepper`).
    Checkpoint/resume is not wired for this path yet — pass batches
    through the CSR stream (:func:`train_linear_model_stream` with
    ``sparse_dim``) if you need durable mid-fit state."""
    del mesh, cache_dir, memory_budget_bytes, prefetch_depth
    from flinkml_tpu.iteration.runtime import TerminateOnMaxIterOrTol
    from flinkml_tpu.table import SortedSparseColumn

    if loss not in _LOSS_KEYS:
        raise ValueError(f"loss must be one of {_LOSS_KEYS}, got {loss!r}")
    if checkpoint_manager is not None or resume or checkpoint_interval:
        raise ValueError(
            "checkpoint/resume is not supported on the sorted-column "
            "stream path; use the CSR stream (sparse_dim=...) for "
            "durable fits"
        )
    dt = jnp.dtype(dtype)
    l2 = reg * (1.0 - elastic_net)
    l1 = reg * elastic_net
    hy = (
        jnp.asarray(learning_rate, dt),
        jnp.asarray(l2, dt),
        jnp.asarray(l1, dt),
    )
    criterion = TerminateOnMaxIterOrTol(max_iter, tol)

    stepper = None
    coef = None
    dim = None
    ones_cache = {}  # bucket -> device ones, for weightless streams

    def step_table(t, coef, first_pass: bool):
        nonlocal stepper, dim
        col = t._raw_column(features_col)
        if not isinstance(col, SortedSparseColumn):
            raise ValueError(
                f"sorted-column stream: feature column {features_col!r} "
                "is not a SortedSparseColumn (feed the stream through "
                "data.prefetch.DevicePrefetcher)"
            )
        if dim is None:
            dim = col.dim
            stepper = _sorted_column_stepper(loss, dim)
            coef = jnp.zeros(dim, dt)
        elif col.dim != dim:
            raise ValueError(
                f"stream batch feature dimension {col.dim} != first "
                f"batch's {dim}"
            )
        yraw = t._raw_column(label_col)
        yb = yraw.buf if hasattr(yraw, "buf") else jnp.asarray(yraw)
        if first_pass and label_check is not None:
            label_check(np.asarray(yb)[: col.rows])
        if weight_col is not None and weight_col in t.column_names:
            wraw = t._raw_column(weight_col)
            wb = wraw.buf if hasattr(wraw, "buf") else jnp.asarray(wraw)
        else:
            bucket = col.buf.shape[0]
            wb = ones_cache.get(bucket)
            if wb is None:
                wb = ones_cache.setdefault(bucket, jnp.ones(bucket, dt))
        if first_pass:
            if validate is not None:
                validate(t)
            if col.rows == 0 or float(np.asarray(wb)[: col.rows].sum()) == 0:
                raise ValueError(
                    "stream batch has zero total weight (empty batch or "
                    "all weights 0); drop such batches before training"
                )
        n_valid = jnp.asarray(col.rows, jnp.int32)
        return stepper(coef, col.indices, col.buf, col.perm,
                       col.segment_ids, yb, wb, n_valid, *hy)

    epoch = 0
    cur_loss = math.inf
    cache = []

    def run_epoch(batch_iter, coef, first_pass):
        loss_acc = jnp.zeros((), dt)
        wsum_acc = jnp.zeros((), dt)
        n_batches = 0
        for t in batch_iter:
            if first_pass:
                cache.append(t)
            coef, ls, ws = step_table(t, coef, first_pass)
            loss_acc = loss_acc + ls
            wsum_acc = wsum_acc + ws
            n_batches += 1
        if n_batches == 0:
            raise ValueError("training stream is empty")
        return coef, float(loss_acc) / float(wsum_acc)

    def after_epoch():
        coef_host = np.asarray(coef)
        for listener in listeners:
            listener.on_epoch_watermark_incremented(epoch - 1, coef_host)

    coef, cur_loss = run_epoch(tables, coef, True)
    epoch = 1
    after_epoch()
    while not criterion.should_terminate(epoch - 1, cur_loss):
        coef, cur_loss = run_epoch(cache, coef, False)
        epoch += 1
        after_epoch()

    result = np.asarray(coef)
    for listener in listeners:
        listener.on_iteration_terminated(result)
    return result


def _ell_width_for(max_nnz: int) -> int:
    """Quantize a batch's max nnz up to the next power of two, so the
    stream's per-batch nnz variation maps to a log-bounded set of
    compiled step shapes, not one per batch."""
    return 1 << max(int(max_nnz) - 1, 0).bit_length()


def _pack_uniform_ell(indptr, indices, values, dtype, width=None):
    """Pack one CSR batch into uniform ELL (width quantized via
    :func:`_ell_width_for` unless an agreed ``width`` is given — the
    multi-process path fixes ONE global width). Padding cells carry
    index 0 / value 0 (exact no-ops)."""
    from flinkml_tpu.ops.sparse import fill_ell

    indptr = np.asarray(indptr, dtype=np.int64)
    n = indptr.size - 1
    nnz = np.diff(indptr)
    if width is None:
        width = _ell_width_for(np.max(nnz, initial=1))
    bi = np.zeros((n, width), dtype=np.int32)
    bv = np.zeros((n, width), dtype=dtype)
    fill_ell(bi, bv, indptr[:-1], nnz, indices, values)
    return bi, bv


def _pad_rows(arr: np.ndarray, rows: int) -> np.ndarray:
    if arr.shape[0] == rows:
        return arr
    pad = [(0, rows - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, pad)


_DUMMY_BATCH = {"_dummy": True}


def _train_linear_stream_multiprocess(
    batches,
    loss: str,
    mesh: DeviceMesh,
    max_iter: int,
    learning_rate: float,
    reg: float,
    elastic_net: float,
    tol: float,
    cache_dir: Optional[str],
    memory_budget_bytes: Optional[int],
    checkpoint_manager,
    checkpoint_interval: int,
    resume: bool,
    listeners,
    prefetch_depth: int,
    dtype,
    columns: Tuple[str, str, Optional[str]],
    validate,
) -> np.ndarray:
    """The multi-process body of :func:`train_linear_model_stream`.

    Each process feeds its OWN partition of the stream (the reference's
    per-subtask stream partitions); the SPMD invariants — one agreed
    padded batch height, one agreed step count per epoch, zero-weight
    dummy steps for short processes — come from
    :class:`~flinkml_tpu.iteration.stream_sync.SyncedReplayPlan`.
    Differences from the single-process path, all forced by SPMD:

      - pass 0 caches WITHOUT training (the step count must be agreed
        before the first collective dispatch), so one extra replay pass;
      - every step has one fixed global shape (bounds compilations to 1);
      - in-flight dispatches are bounded by
        :class:`~flinkml_tpu.parallel.dispatch.DispatchGuard` (the
        multi-process backpressure policy);
      - checkpoints commit rank-0-writes + global barrier
        (:func:`~flinkml_tpu.iteration.checkpoint.save_replicated`)
        against a SHARED checkpoint directory.

    Numerics match a single-process run whose step-t batch is the
    concatenation of every process's step-t batch (up to float reduction
    order); the fitted coefficient is replicated and identical on every
    process.
    """
    from flinkml_tpu.iteration.checkpoint import begin_resume, save_replicated
    from flinkml_tpu.iteration.datacache import (
        DataCache,
        DataCacheWriter,
        PrefetchingDeviceFeed,
    )
    from flinkml_tpu.iteration.runtime import TerminateOnMaxIterOrTol
    from flinkml_tpu.iteration.stream_sync import (
        DeferredValidation,
        SyncedReplayPlan,
        agree_feature_dim,
        checked_ingest,
    )
    from flinkml_tpu.parallel.dispatch import DispatchGuard

    # loss/resume-durability already validated by the dispatching caller
    # (train_linear_model_stream).
    is_cache = isinstance(batches, DataCache)
    resume_epoch = begin_resume(checkpoint_manager, resume, mesh.mesh.size)

    p_size = mesh.axis_size()
    row_tile = p_size * 8
    axis = DeviceMesh.DATA_AXIS
    stepper = _stream_stepper(mesh.mesh, loss, axis)
    l2 = reg * (1.0 - elastic_net)
    l1 = reg * elastic_net
    x_key, y_key, w_key = columns

    # -- pass 0: cache only (step counts must be agreed before training) --
    dv = DeferredValidation()
    first_dim = [None]

    def check_ingest(b):
        """Everything place-time validation would catch — a place-time
        raise on the feed thread is rank-local mid-collective (the hang
        class DeferredValidation prevents), so iterable sources must be
        FULLY validated here: x shape/dim consistency, label-column
        presence, zero total weight, plus the estimator's hook."""
        x = np.asarray(b[x_key], dtype=dtype)
        np.asarray(b[y_key], dtype=dtype)  # missing label column raises
        if x.ndim != 2:
            raise ValueError(
                f"stream batches must be [n, d], got {x.shape}"
            )
        if first_dim[0] is None:
            first_dim[0] = x.shape[1]
        elif x.shape[1] != first_dim[0]:
            raise ValueError(
                f"batch feature dim {x.shape[1]} != first batch's "
                f"{first_dim[0]}"
            )
        if validate is not None:
            validate(b)
        w = (
            np.asarray(b[w_key], dtype=dtype)
            if w_key is not None and w_key in b
            else np.ones(x.shape[0], dtype=dtype)
        )
        if x.shape[0] == 0 or float(w.sum()) == 0.0:
            raise ValueError(
                "stream batch has zero total weight (empty batch or all "
                "weights 0); drop such batches before training"
            )

    if is_cache:
        cache = batches
    else:

        writer = DataCacheWriter(cache_dir, memory_budget_bytes)

        def checked_append(b):
            # Validation, the column copies, AND the append are one
            # checked step: a ragged value's np.array ValueError or a
            # rank-local writer failure (disk full while spilling) is
            # held for the rendezvous, never raised rank-locally.
            check_ingest(b)
            writer.append({k: np.array(v) for k, v in b.items()})

        # This trainer IS the multi-process path (dispatched on
        # process_count > 1), so iterator and ingest failures always
        # ride the rendezvous.
        for _ in checked_ingest(batches, dv, checked_append, multi=True):
            pass
        cache = writer.finish()

    # Rendezvous BEFORE planning: a held ingest error must surface as
    # itself, not as plan.create's "stream is empty on every process"
    # (skip-on-failure can leave every local cache empty).
    dv.rendezvous(mesh, "stream ingest validation")
    plan = SyncedReplayPlan.create(cache, mesh, row_tile)
    height = plan.local_height
    dim = agree_feature_dim(cache, x_key, mesh)

    # Iterable sources were fully validated at ingest (above, before the
    # rendezvous); only sealed caches still validate at first replay —
    # those raises are rank-local on the feed thread, the documented
    # residual (stream_sync.DeferredValidation).
    first_pass_done = [not is_cache]

    def place(batch):
        if "_dummy" in batch:
            x = np.zeros((height, dim), dtype)
            y = np.zeros(height, dtype)
            w = np.zeros(height, dtype)
        else:
            x = np.asarray(batch[x_key], dtype=dtype)
            y = np.asarray(batch[y_key], dtype=dtype)
            w = (
                np.asarray(batch[w_key], dtype=dtype)
                if w_key is not None and w_key in batch
                else np.ones(x.shape[0], dtype=dtype)
            )
            if not first_pass_done[0]:
                if validate is not None:
                    validate(batch)
                if x.shape[0] == 0 or float(w.sum()) == 0.0:
                    raise ValueError(
                        "stream batch has zero total weight (empty batch or "
                        "all weights 0); drop such batches before training"
                    )
            from flinkml_tpu.iteration.stream_sync import pad_rows_to

            x, y, w = (
                pad_rows_to(x, height),
                pad_rows_to(y, height),
                pad_rows_to(w, height),
            )
        return (
            mesh.global_batch(x),
            mesh.global_batch(y),
            mesh.global_batch(w),
        )

    dt = jnp.dtype(dtype)
    hy = (
        jnp.asarray(learning_rate, dt),
        jnp.asarray(l2, dt),
        jnp.asarray(l1, dt),
    )
    criterion = TerminateOnMaxIterOrTol(max_iter, tol)

    coef = None
    epoch = 0
    cur_loss = math.inf
    if resume_epoch is not None:
        restored = _restore_carry(checkpoint_manager, dim, dtype, mesh)
        if restored is not None:
            coef_h, epoch, cur_loss = restored
            coef = jnp.asarray(coef_h, dt)

    def mark_validated():
        first_pass_done[0] = True

    return _run_multiprocess_stream_epochs(
        cache, plan, place, stepper, dim, hy, dt, criterion,
        checkpoint_manager, checkpoint_interval, listeners, prefetch_depth,
        mesh, coef, epoch, cur_loss, after_first_epoch=mark_validated,
    )


def train_linear_model_stream(
    batches,
    loss: str,
    mesh: DeviceMesh,
    max_iter: int,
    learning_rate: float,
    reg: float,
    elastic_net: float,
    tol: float,
    cache_dir: Optional[str] = None,
    memory_budget_bytes: Optional[int] = None,
    checkpoint_manager=None,
    checkpoint_interval: int = 0,
    resume: bool = False,
    listeners=(),
    prefetch_depth: int = 2,
    dtype=np.float32,
    columns: Tuple[str, str, Optional[str]] = ("x", "y", "w"),
    validate=None,
    sparse_dim: Optional[int] = None,
) -> np.ndarray:
    """Train from a one-shot stream of batches, datasets larger than RAM
    included — the round-2 integration of the datacache subsystem into a
    product fit path (round-1 VERDICT "missing" #1).

    ``columns`` names the (features, label, weight) keys inside each batch
    dict; a ``None``/absent weight key defaults to unit weights.
    ``validate`` (optional) is called with each host batch dict before
    device placement — the hook estimators use for per-batch input checks
    (e.g. binomial labels), which must also cover batches that only exist
    inside a caller-provided :class:`DataCache`.

    ``sparse_dim`` (round 5, the Criteo-1TB-shaped gap): when set, each
    batch is a FLAT CSR dict — top-level keys ``indptr`` / ``indices`` /
    ``values`` / ``y`` / ``w`` (optional) / ``dim``, each stored as one
    2-D row so the cache's equal-row-count contract holds — cached AS
    CSR (O(nnz) disk/RAM, not O(n·dim)), packed per batch into
    power-of-two-width uniform ELL at place time, and trained through
    :func:`_sparse_stream_stepper` against the dense replicated
    ``[sparse_dim]`` coefficient. Multi-process meshes route to
    :func:`_train_linear_sparse_stream_multiprocess` (per-rank CSR
    partitions, agreed schedule + global ELL width).

    Reference parity: ``ReplayOperator.java:62-250`` — epoch 0 caches the
    data stream to ``DataCacheWriter`` segments AND forwards it to training;
    every later epoch replays the cache. Here:

      - ``batches``: an iterable of ``{"x": [n,d], "y": [n], "w": [n]}``
        numpy dicts (one global mini-batch each), OR an already-sealed
        :class:`~flinkml_tpu.iteration.datacache.DataCache` of such batches
        (then no epoch-0 caching pass is needed, and ``resume=True`` is
        allowed — the cache is durable, so a restored run replays it).
      - epoch 0 trains batch-by-batch while appending each batch to the
        cache; batches beyond ``memory_budget_bytes`` spill to segment
        files under ``cache_dir``.
      - epochs 1..: replay through
        :class:`~flinkml_tpu.iteration.datacache.PrefetchingDeviceFeed`,
        overlapping the next batch's host→HBM transfer with the current
        step (the TPU answer to the reference's credit-based network
        buffering).
      - spilled and RAM-resident replay are bit-identical (raw columnar
        segments round-trip exactly), so the memory budget is a pure
        capacity knob, never a numerics knob.

    Each batch is padded to the mesh row tile with weight-0 rows (exact:
    zero weight ⇒ zero contribution to grad/loss/wsum) and sharded over the
    data axis. Termination is ``TerminateOnMaxIterOrTol(max_iter, tol)`` on
    the weighted epoch-mean loss. ``checkpoint_interval`` K snapshots
    ``(coef, loss)`` every K epochs.
    """
    from flinkml_tpu.iteration.datacache import (
        DataCache,
        DataCacheWriter,
        PrefetchingDeviceFeed,
    )

    if loss not in _LOSS_KEYS:
        raise ValueError(f"loss must be one of {_LOSS_KEYS}, got {loss!r}")
    is_cache = isinstance(batches, DataCache)
    if resume and not is_cache:
        raise ValueError(
            "resume=True requires a durable DataCache input: a one-shot "
            "stream cannot be replayed from the start after a failure"
        )
    if jax.process_count() > 1:
        if sparse_dim is not None:
            # Per-process CSR partitions + agreed SPMD schedule with one
            # extra agreed quantity (the global ELL width).
            return _train_linear_sparse_stream_multiprocess(
                batches, loss, mesh, max_iter, learning_rate, reg,
                elastic_net, tol, cache_dir, memory_budget_bytes,
                checkpoint_manager, checkpoint_interval, resume,
                listeners, prefetch_depth, dtype, validate,
                int(sparse_dim),
            )
        # Per-process stream partitions + agreed SPMD schedule; see
        # _train_linear_stream_multiprocess for the invariants.
        return _train_linear_stream_multiprocess(
            batches, loss, mesh, max_iter, learning_rate, reg, elastic_net,
            tol, cache_dir, memory_budget_bytes, checkpoint_manager,
            checkpoint_interval, resume, listeners, prefetch_depth, dtype,
            columns, validate,
        )
    from flinkml_tpu.iteration.checkpoint import begin_resume

    begin_resume(checkpoint_manager, resume, mesh.mesh.size)

    p_size = mesh.axis_size()
    row_tile = p_size * 8  # bounds the set of padded shapes → compilations
    axis = DeviceMesh.DATA_AXIS
    stepper = (
        _sparse_stream_stepper(mesh.mesh, loss, axis, int(sparse_dim))
        if sparse_dim is not None
        else _stream_stepper(mesh.mesh, loss, axis)
    )
    l2 = reg * (1.0 - elastic_net)
    l1 = reg * elastic_net

    x_key, y_key, w_key = columns
    # Batches are immutable once cached, so input validation only needs the
    # first pass — not max_iter re-scans on the prefetch thread.
    first_pass_done = False

    def extract_yw(batch, n):
        y = np.asarray(batch[y_key], dtype=dtype)
        w = (
            np.asarray(batch[w_key], dtype=dtype)
            if w_key is not None and w_key in batch
            else np.ones(n, dtype=dtype)
        )
        if not first_pass_done:
            if validate is not None:
                validate(batch)
            if n == 0 or float(w.sum()) == 0.0:
                # The stepper divides by the batch weight sum; an inf step
                # size would silently NaN the whole model. Fail loudly.
                raise ValueError(
                    "stream batch has zero total weight (empty batch or all "
                    "weights 0); drop such batches before training"
                )
        return y, w

    def place(batch):
        x = np.asarray(batch[x_key], dtype=dtype)
        y, w = extract_yw(batch, x.shape[0])
        rows = max(row_tile, -(-x.shape[0] // row_tile) * row_tile)
        return (
            mesh.shard_batch(_pad_rows(x, rows)),
            mesh.shard_batch(_pad_rows(y, rows)),
            mesh.shard_batch(_pad_rows(w, rows)),
        )

    def place_sparse(batch):
        # Flat CSR batch format: every component is one 2-D row (the
        # cache's columnar contract wants equal row counts per batch,
        # and CSR components have different lengths by nature).
        indptr = np.asarray(batch["indptr"])[0]
        n = indptr.size - 1
        y = np.asarray(batch["y"])[0].astype(dtype)
        w = (
            np.asarray(batch["w"])[0].astype(dtype)
            if "w" in batch else np.ones(n, dtype=dtype)
        )
        if not first_pass_done:
            d = int(np.asarray(batch["dim"]).reshape(-1)[0])
            if d != sparse_dim:
                # The stepper is compiled against sparse_dim; indices
                # from a different feature space would silently clamp/
                # drop in the gather and scatter.
                raise ValueError(
                    f"CSR stream batch has dim {d}, expected {sparse_dim}"
                )
            _check_csr_structure(
                indptr, np.asarray(batch["indices"])[0], sparse_dim
            )
            if validate is not None:
                validate(batch)
            if n == 0 or float(w.sum()) == 0.0:
                raise ValueError(
                    "stream batch has zero total weight (empty batch or "
                    "all weights 0); drop such batches before training"
                )
        bi, bv = _pack_uniform_ell(
            indptr, np.asarray(batch["indices"])[0],
            np.asarray(batch["values"])[0], dtype,
        )
        rows = max(row_tile, -(-n // row_tile) * row_tile)
        # Row padding: index 0 / value 0 / weight 0 — exact no-ops.
        return (
            mesh.shard_batch(_pad_rows(bi, rows)),
            mesh.shard_batch(_pad_rows(bv, rows)),
            mesh.shard_batch(_pad_rows(y, rows)),
            mesh.shard_batch(_pad_rows(w, rows)),
        )

    if sparse_dim is not None:
        place = place_sparse

    from flinkml_tpu.iteration.runtime import TerminateOnMaxIterOrTol

    dt = jnp.dtype(dtype)
    hy = (
        jnp.asarray(learning_rate, dt),
        jnp.asarray(l2, dt),
        jnp.asarray(l1, dt),
    )
    criterion = TerminateOnMaxIterOrTol(max_iter, tol)

    coef = None
    epoch = 0  # epochs completed
    cur_loss = math.inf

    def run_epoch(device_batches, coef):
        """One pass; returns (coef, epoch mean loss). Accumulates the loss
        on device so only the per-epoch conversion synchronizes."""
        loss_acc = jnp.zeros((), dt)
        wsum_acc = jnp.zeros((), dt)
        n_batches = 0
        for tensors in device_batches:
            if coef is None:
                d0 = (sparse_dim if sparse_dim is not None
                      else tensors[0].shape[1])
                coef = jnp.zeros(d0, dt)
            coef, ls, ws = stepper(coef, *tensors, *hy)
            loss_acc = loss_acc + ls
            wsum_acc = wsum_acc + ws
            n_batches += 1
        if n_batches == 0:
            raise ValueError("training stream is empty")
        return coef, float(loss_acc) / float(wsum_acc)

    def after_epoch(terminated: bool):
        """Shared per-epoch bookkeeping (listeners + checkpoint), run after
        `epoch` has been advanced to the completed-epoch count. With a
        manager, the terminal carry is ALWAYS saved (matching
        ``_run_chunked``), even when no interval was configured."""
        nonlocal first_pass_done
        first_pass_done = True
        coef_host = np.asarray(coef)
        for listener in listeners:
            listener.on_epoch_watermark_incremented(epoch - 1, coef_host)
        if checkpoint_manager is not None and (
            terminated
            or (checkpoint_interval > 0 and epoch % checkpoint_interval == 0)
        ):
            checkpoint_manager.save((coef_host, np.float64(cur_loss)), epoch)

    # -- epoch 0: cache + train (ReplayOperator epoch-0 semantics), unless
    # the caller handed us a sealed cache (then every epoch replays it). ---
    if is_cache:
        cache = batches
        if resume:
            if sparse_dim is not None:
                dim = int(sparse_dim)
            else:
                first = next(iter(cache.reader()))
                dim = np.asarray(first[x_key]).shape[1]
            restored = _restore_carry(checkpoint_manager, dim, dtype, mesh)
            if restored is not None:
                coef_h, epoch, cur_loss = restored
                coef = jnp.asarray(coef_h, dt)
    else:
        writer = DataCacheWriter(cache_dir, memory_budget_bytes)

        def caching_iter():
            for b in batches:
                # Copy: the writer freezes RAM-resident arrays against
                # mutation, and that must not leak onto caller-owned
                # buffers that outlive the fit.
                writer.append({k: np.array(v) for k, v in b.items()})
                yield b

        feed0 = PrefetchingDeviceFeed(caching_iter(), place=place,
                                      depth=prefetch_depth)
        try:
            coef, cur_loss = run_epoch(feed0, coef)
        finally:
            feed0.close()
        cache = writer.finish()
        epoch = 1
        after_epoch(criterion.should_terminate(0, cur_loss))

    # -- remaining epochs: replay the cache through the prefetching feed ----
    while not (epoch > 0 and criterion.should_terminate(epoch - 1, cur_loss)):
        feed = PrefetchingDeviceFeed(cache.reader(), place=place,
                                     depth=prefetch_depth)
        try:
            coef, cur_loss = run_epoch(feed, coef)
        finally:
            feed.close()
        epoch += 1
        after_epoch(criterion.should_terminate(epoch - 1, cur_loss))

    result = np.asarray(coef)
    if checkpoint_manager is not None:
        checkpoint_manager.wait()  # surface a failed final async write
    for listener in listeners:
        listener.on_iteration_terminated(result)
    return result
