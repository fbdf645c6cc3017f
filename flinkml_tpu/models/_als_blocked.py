"""``ALS.fit(Table)``: the ratings in both orders kept on the mesh WITH
the table, a half-step solved by target block.

For a target (a user in the first half of an iteration, an item in the
second) with ratings ``r_j`` of fixed-side rows ``y_j``, ALS-WR solves ::

    (sum_j a_j y_j y_j' + G + lam I) x = sum_j b_j y_j
    lam = max(regParam * max(n, 1), 1e-4)

explicit: ``a_j`` 1, ``b_j`` ``r_j``, ``G`` 0; implicit (Hu, Koren,
Volinsky): ``a_j`` ``alpha r_j``, ``b_j`` ``1 + alpha r_j``, ``G`` the
fixed side's ``Y'Y``. The streamed fit (``models/als.py``) scatters
``[ratings, k, k]`` outer products into ``A [targets, k, k]``: 40 GB at a
million users of rank 100. Here neither array exists:

- **Ingest, once a table** (:func:`ingest`): the vocabularies by presence
  over ``[min, max]`` where the ids are integers in a span of the order
  of the ratings (``np.unique`` otherwise, and the same answer), each
  order of the ratings by a threaded sort of packed (target, position)
  keys merged by counting; a table grouped by one side is seen as such.
- **Laid by degree** (:func:`plan_side`): a target with ``n`` ratings
  gets ``L`` slots, the next length of a ladder (8, 16, 32, 48, 64, 96,
  ... in steps of at most 1.5); the slots past ``n`` name the fixed
  side's zero row. Targets of one length are dealt over the devices in
  turn and walked a chunk of ``c`` at a time (``c * L`` slots at most
  what :func:`chunk_slots` read off the device's free memory). A target
  with more ratings than a chunk holds is cut in pieces of a whole chunk
  each, whose partial sums are added before the solve.
- **A chunk** (:func:`_chunk_systems`): the slots' fixed-side rows ``Y
  [c, L, 128]`` fetched (rank padded to 128 lanes, lane ``k`` free), ONE
  batched product ``Y' [a Y | b]`` at :data:`GRAM_PRECISION` giving ``A``
  and the right-hand side together as ``[c, k, k + 1]``, regularised,
  solved (:func:`_solve`: on a TPU ``kernels.spd_solve``, a system a
  lane; elsewhere XLA's Cholesky), ``[c, k]`` written.
- **A half-step** is one program, ``als_half_step``: the buckets' chunks
  in loops, each device its own targets from the replicated fixed side,
  one ``all_gather`` of the solved rows, the rows put in id order.
  ``regParam`` and ``alpha`` are operands.
- **Kept with the table** (:meth:`Table.device_resident`): both orders'
  slots, counts and row maps, placed once through
  :meth:`DeviceMesh.stage_rows`.

Spans and counters: ``docs/development/observability.md`` (``als.*``, the
group ``als``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from flinkml_tpu.kernels import _gate, spd_solve
from flinkml_tpu.parallel import DeviceMesh
from flinkml_tpu.parallel.mesh import _GATHER_THREADS, gather_pool
from flinkml_tpu.table import _free_bytes
from flinkml_tpu.utils.metrics import metrics
from flinkml_tpu.utils.profiling import named_program, phase, span

#: The precision of the Gram and right-hand-side products: float32's own
#: sums (six bfloat16 products on a TPU). One bfloat16 pass (``DEFAULT``)
#: rounds every factor read to 8 bits: the benchmark's control.
GRAM_PRECISION = jax.lax.Precision.HIGHEST
#: The half-step's phases (``profiling.phase``): a chunk's or a piece's
#: rows fetched, its Gram products, its solves. The pieces' sums, the
#: ``all_gather`` and the id order are in none.
PHASES = ("als.fetch", "als.gram", "als.solve")

#: A factor row's lanes: the rank padded, so that a fetched row is whole
#: vregs and lane ``rank`` is free for a rating's right-hand-side weight.
LANES = 128
#: Most targets a chunk: its ``[c, 128, 128]`` product is 64 KB a target.
_CHUNK_TARGETS = 1024
#: Slots a chunk where the device reports no memory (a CPU), and the most
#: anywhere: 128 MB of fetched rows.
_CHUNK_SLOTS = 1 << 18
#: What a slot of a chunk costs in scratch, reckoned generously: its
#: fetched row, the weighted copy, the product's parts.
_SLOT_SCRATCH_BYTES = 32 << 10
_LAM_FLOOR = 1e-4


class _Side(NamedTuple):
    """One order of the ratings as a device's loops read it."""

    idx: jax.Array        # [p * slots_local] int32: fixed-side rows, the zero row at padding
    val: jax.Array        # [p * slots_local] float32 ratings, 0 at padding
    counts: jax.Array     # [p * rows_local] float32 ratings a local target
    owner: jax.Array      # [p * pieces] int32: a piece's local cut target
    where: jax.Array      # [targets] int32: a target's row of the gathered solved rows
    plan: Tuple           # ((length, chunk, chunks), ...), (pieces, cut targets), slots a piece
    targets: int
    slots: int            # all devices', padding included


class _Placed(NamedTuple):
    by_user: _Side
    by_item: _Side
    user_ids: np.ndarray
    item_ids: np.ndarray
    ratings: int


class _Order(NamedTuple):
    """The ratings grouped by one side: target ``t``'s are positions
    ``order[indptr[t]:indptr[t + 1]]`` of the table, in the table's
    order (``order`` None: the table is grouped already)."""

    indptr: np.ndarray
    order: Optional[np.ndarray]


# -- ingest ------------------------------------------------------------------

def _parts(pool, x, fn):
    """``fn`` over contiguous parts of ``x`` on the pool's threads, in
    order (NumPy's passes release the interpreter lock)."""
    return list(pool.map(fn, np.array_split(x, _GATHER_THREADS)))


def vocabulary(raw: np.ndarray):
    """``np.unique(raw, return_inverse=True)`` with the inverse as int32,
    without sorting the ratings where the ids are integers whose span is
    of the order of their number: presence over ``[min, max]``."""
    raw = np.asarray(raw)
    if raw.size and raw.dtype.kind in "iu":
        lo, hi = int(raw.min()), int(raw.max())
        if hi - lo < 4 * raw.size + 1024:
            seen = np.zeros(hi - lo + 1, bool)
            with gather_pool() as pool:
                _parts(pool, raw, lambda part: seen.__setitem__(part - lo, True))
                if seen.all():
                    index = raw if lo == 0 else np.concatenate(
                        _parts(pool, raw, lambda part: part - lo))
                else:
                    rank = np.cumsum(seen, dtype=np.int32) - 1
                    index = np.concatenate(
                        _parts(pool, raw, lambda part: rank[part - lo]))
            ids = (np.flatnonzero(seen) + lo).astype(raw.dtype)
            return ids, index.astype(np.int32, copy=False)
    ids, index = np.unique(raw, return_inverse=True)
    return ids, index.reshape(-1).astype(np.int32)


def group(index: np.ndarray, n: int) -> _Order:
    """The ratings ordered by ``index`` (a side's vocabulary positions,
    all below ``n``), stably. A table grouped by that side already needs
    one look. Any other is cut in parts: each part's packed (target,
    position) keys are sorted on a thread (the keys are distinct, so any
    sort is stable), and the parts merged by counting: a part's run of a
    target goes behind the earlier parts' runs of it."""
    nnz = index.size
    if nnz < 2 or bool(np.all(index[1:] >= index[:-1])):
        return _Order(np.searchsorted(index, np.arange(n + 1)).astype(np.int64), None)
    if nnz >= (1 << 32):   # a position no longer fits a key's low half
        order = np.argsort(index, kind="stable")
        return _Order(np.searchsorted(index[order], np.arange(n + 1)).astype(np.int64),
                      order)
    edges = np.linspace(0, nnz, _GATHER_THREADS + 1).astype(np.int64)

    def sort_part(a):
        lo, hi = edges[a], edges[a + 1]
        keys = index[lo:hi].astype(np.uint64)
        keys <<= np.uint64(32)
        keys |= np.arange(lo, hi, dtype=np.uint64)
        keys.sort()
        # Sorted, so a target's count is the distance of two searches.
        return keys, np.diff(np.searchsorted(
            keys, np.arange(n + 1, dtype=np.uint64) << np.uint64(32)))

    order = np.empty(nnz, np.int64 if nnz >= (1 << 31) else np.int32)
    with gather_pool() as pool:
        sorted_parts = list(pool.map(sort_part, range(_GATHER_THREADS)))
        counts = np.stack([own for _, own in sorted_parts])        # [parts, n]
        indptr = _indptr(counts.sum(axis=0))
        # Where a part's run of a target starts in the whole order.
        starts = indptr[:-1][None, :] + np.cumsum(counts, axis=0) - counts

        def merge_part(a):
            keys, own = sorted_parts[a]
            dest = np.repeat(starts[a] - (np.cumsum(own) - own), own)
            dest += np.arange(keys.size)
            order[dest] = (keys & np.uint64(0xFFFFFFFF)).astype(order.dtype)

        list(pool.map(merge_part, range(_GATHER_THREADS)))
    return _Order(indptr, order)


def _indptr(counts) -> np.ndarray:
    indptr = np.zeros(counts.size + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    return indptr


# -- the layout --------------------------------------------------------------

def chunk_slots(devices, table_bytes: int) -> int:
    """Slots a chunk of a half-step walks: a power of two, from what the
    devices report free once ``table_bytes`` more are on each
    (:data:`_SLOT_SCRATCH_BYTES` a slot within an eighth of it), at most
    :data:`_CHUNK_SLOTS`, which is also a device's that reports nothing."""
    free = [_free_bytes(d) for d in devices]
    if None in free:
        return _CHUNK_SLOTS
    room = max(0, min(free) - table_bytes) // 8 // _SLOT_SCRATCH_BYTES
    return min(_CHUNK_SLOTS, 1 << max(12, int(room).bit_length() - 1))


def ladder(most: int) -> np.ndarray:
    """The lengths a target's slots are padded to, up to ``most`` (a
    power of two): 8, 16, 32, then steps of at most 1.5."""
    lengths = [8, 16] + [m << e for e in range(4, 40) for m in (2, 3)]
    return np.asarray([length for length in lengths if length < most] + [most],
                      np.int64)


class _Plan(NamedTuple):
    """Where every target of one side lies: ``device``, the first of its
    slots among its device's (``slot``), its row of its device's solved
    rows (``row``); and the static ``plan`` the program is built from."""

    plan: Tuple
    device: np.ndarray
    slot: np.ndarray
    row: np.ndarray
    slots_local: int
    rows_local: int
    owner: np.ndarray     # [p, pieces] int32


def plan_side(degrees: np.ndarray, p: int, piece: int) -> _Plan:
    """Targets of ``degrees`` ratings over ``p`` devices, a chunk at most
    ``piece`` slots (the module docstring's third point). A target with
    no rating lies nowhere (its ``row`` is the zero row behind all
    devices' rows)."""
    n = degrees.size
    lengths = ladder(piece)
    which = np.searchsorted(lengths, degrees)
    device = np.zeros(n, np.int64)
    slot, row = np.zeros(n, np.int64), np.zeros(n, np.int64)
    buckets, slots, rows = [], 0, 0
    for b, length in enumerate(lengths.tolist()):
        members = np.flatnonzero((which == b) & (degrees > 0))
        if not members.size:
            continue
        each = -(-members.size // p)
        chunk = min(_CHUNK_TARGETS, max(1, piece // length), each)
        chunks = -(-each // chunk)
        turn = np.arange(members.size)
        device[members] = turn % p
        slot[members] = slots + turn // p * length
        row[members] = rows + turn // p
        buckets.append((length, chunk, chunks))
        slots += chunks * chunk * length
        rows += chunks * chunk
    # Targets cut in pieces: each device's in turn, a target's pieces
    # one behind the other.
    cut = np.flatnonzero(which == lengths.size)
    each = -(-cut.size // p)
    pieces_of = -(-degrees[cut] // piece)
    turn = np.arange(cut.size)
    device[cut] = turn % p
    row[cut] = rows + turn // p
    owner = []
    for d in range(p):
        mine = turn % p == d
        before = np.cumsum(pieces_of[mine]) - pieces_of[mine]
        slot[cut[mine]] = slots + before * piece
        owner.append(np.repeat(np.arange(before.size), pieces_of[mine]))
    most_pieces = max(o.size for o in owner)
    # A piece of the padding names no target (``each``); one column at
    # least, so that no array is empty.
    owner = np.stack([np.concatenate([o, np.full(max(1, most_pieces) - o.size, each)])
                      for o in owner]).astype(np.int32)
    rows_local = rows + each
    row[degrees == 0] = p * rows_local
    return _Plan((tuple(buckets), (most_pieces, each), piece), device, slot, row,
                 slots + most_pieces * piece, rows_local, owner)


def _slot_order(order: _Order, plan: _Plan, p: int, sentinel: int) -> np.ndarray:
    """For every slot of every device the position of its rating in the
    table's columns, ``sentinel`` at the padding."""
    indptr = order.indptr
    nnz = int(indptr[-1])
    out = np.full(p * plan.slots_local, sentinel,
                  np.int64 if sentinel >= (1 << 31) else np.int32)
    shift = plan.device * plan.slots_local + plan.slot - indptr[:-1]
    # Ranges of targets that hold about as many ratings each.
    cuts = np.searchsorted(indptr, np.linspace(0, nnz, 4 * _GATHER_THREADS + 1))
    cuts[0], cuts[-1] = 0, indptr.size - 1

    def lay(a):
        t0, t1 = cuts[a], cuts[a + 1]
        q0, q1 = indptr[t0], indptr[t1]
        dest = np.repeat(shift[t0:t1], np.diff(indptr[t0:t1 + 1]))
        dest += np.arange(q0, q1)
        out[dest] = (np.arange(q0, q1, dtype=out.dtype) if order.order is None
                     else order.order[q0:q1])

    with gather_pool() as pool:
        list(pool.map(lay, range(cuts.size - 1)))
    return out


def _place_side(order: _Order, other: np.ndarray, ratings: np.ndarray,
                plan: _Plan, mesh: DeviceMesh):
    """One order on the mesh: ``other`` (the fixed side's positions, the
    zero row's behind them) and ``ratings`` (a 0 behind them) gathered
    into the slots and sent through :meth:`DeviceMesh.stage_rows`."""
    p = mesh.axis_size()
    slots = _slot_order(order, plan, p, other.size - 1)
    *_, ((idx, val), _) = mesh.stage_rows(
        [(other, slots, np.int32), (ratings, slots, np.float32)])
    degrees = np.diff(order.indptr)
    held = degrees > 0
    counts = np.zeros((p, plan.rows_local), np.float32)
    counts[plan.device[held], plan.row[held]] = degrees[held]
    where = (plan.device * plan.rows_local + plan.row).astype(np.int32)
    return _Side(idx, val, mesh.shard_batch(counts.reshape(-1)),
                 mesh.shard_batch(plan.owner.reshape(-1)), mesh.replicate(where),
                 plan.plan, degrees.size, p * plan.slots_local)


def _with_tail(column: np.ndarray, tail, dtype) -> np.ndarray:
    out = np.empty(column.size + 1, dtype)
    out[:-1] = column
    out[-1] = tail
    return out


def place(users, items, ratings, mesh: DeviceMesh, make_room) -> _Placed:
    """The table's three columns ingested (``als.ingest``) and both orders
    put on the mesh (``als.table_to_device``). ``make_room`` is the
    table's that will keep them, told their bytes before the first array
    is made."""
    p = mesh.axis_size()
    devices = list(mesh.mesh.devices.flat)
    with span("als.ingest"):
        user_ids, u = vocabulary(users)
        item_ids, i = vocabulary(items)
        by_user, by_item = group(u, user_ids.size), group(i, item_ids.size)
        # The chunk is read off the room both orders will leave: 8 bytes
        # a slot, a third more slots than ratings at the most.
        piece = chunk_slots(devices, int(2 * 8 * 4 / 3 * u.size / p))
        plans = (plan_side(np.diff(by_user.indptr), p, piece),
                 plan_side(np.diff(by_item.indptr), p, piece))
        u, i = _with_tail(u, user_ids.size, np.int32), _with_tail(i, item_ids.size, np.int32)
        r = _with_tail(np.asarray(ratings), 0, np.float32)
    nbytes = sum(p * (8 * plan.slots_local + 4 * plan.rows_local + 4 * plan.owner.shape[1])
                 + 4 * plan.row.size for plan in plans)
    make_room(nbytes, devices)
    with span("als.table_to_device") as phase:
        sides = (_place_side(by_user, i, r, plans[0], mesh),
                 _place_side(by_item, u, r, plans[1], mesh))
        phase.add(bytes=nbytes)
    counters = metrics.group("als")
    counters.counter("table_uploads")
    counters.counter("table_h2d_bytes", float(nbytes))
    return _Placed(*sides, user_ids, item_ids, u.size - 1)


# -- the half-step -----------------------------------------------------------

def _solve(aug, rank: int, on_lanes: bool):
    """``x [c, rank]`` of the augmented systems ``aug [c, rank, width]``
    (``A`` in the first ``rank`` columns, ``b`` in column ``rank``)."""
    if not on_lanes:
        a, b = aug[:, :, :rank], aug[:, :, rank]
        return jax.scipy.linalg.cho_solve(
            jax.scipy.linalg.cho_factor(a), b[:, :, None])[:, :, 0]
    # A system a lane, whole blocks of lanes: the identity fills them.
    pad = -aug.shape[0] % spd_solve.LANES
    if pad:
        eye = jnp.eye(rank, aug.shape[2], dtype=aug.dtype)
        aug = jnp.concatenate([aug, jnp.broadcast_to(eye, (pad,) + eye.shape)])
    return spd_solve.solve_lanes(jnp.transpose(aug, (1, 2, 0)), rank).T[:aug.shape[0] - pad]


def make_half_step(plan: Tuple, rank: int, implicit: bool, axis: str,
                   precision=GRAM_PRECISION, on_lanes: bool = False):
    """One device's half-step under ``plan`` (:func:`plan_side`):
    ``(idx, val, counts, owner, where, fixed [n + 1, 128], reg, alpha) ->
    (table [targets + 1, 128], rows [targets, rank])``, the solved
    factors of ALL targets (every device's, gathered), as the next
    half-step's fixed side (lanes padded, the zero row last) and as the
    model holds them."""
    buckets, (pieces, cut), piece = plan
    width = spd_solve.augmented_width(rank)
    f32 = jnp.float32

    def systems(ids, r, fixed, alpha):
        """``[c, rank, width]``: ``A`` and ``b`` of the ``c`` targets
        whose slots ``ids``, ``r`` ``[c, L]`` are, unregularised."""
        with phase("als.fetch"):
            y = fixed.at[ids].get(mode="promise_in_bounds")   # [c, L, 128]
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, LANES), 2)
            if implicit:
                a_w = alpha * r
                right = jnp.where(lane == rank, (1.0 + a_w)[..., None], a_w[..., None] * y)
            else:
                right = jnp.where(lane == rank, r[..., None], y)
        with phase("als.gram"):
            g = jnp.einsum("clk,clm->ckm", y, right, precision=precision,
                           preferred_element_type=f32)
            return g[:, :rank, :width]

    def solved(g, counts, shared, reg):
        with phase("als.solve"):
            lam = jnp.maximum(reg * jnp.maximum(counts, 1.0), _LAM_FLOOR)
            eye = jnp.eye(rank, width, dtype=f32)
            return _solve(g + shared + lam[:, None, None] * eye, rank, on_lanes)

    def half_step(idx, val, counts, owner, where, fixed, reg, alpha):
        if implicit:
            shared = jnp.einsum("nk,nm->km", fixed, fixed,
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=f32)[:rank, :width]
        else:
            shared = jnp.zeros((rank, width), f32)
        out, slot, row = [], 0, 0
        for length, chunk, chunks in buckets:

            def one_chunk(i, slot=slot, row=row, length=length, chunk=chunk):
                at = slot + i * (chunk * length)
                with phase("als.fetch"):
                    ids = jax.lax.dynamic_slice(idx, (at,), (chunk * length,))
                    r = jax.lax.dynamic_slice(val, (at,), (chunk * length,))
                g = systems(ids.reshape(chunk, length), r.reshape(chunk, length),
                            fixed, alpha)
                n = jax.lax.dynamic_slice(counts, (row + i * chunk,), (chunk,))
                return solved(g, n, shared, reg)

            out.append(jax.lax.map(one_chunk, jnp.arange(chunks, dtype=jnp.int32))
                       .reshape(chunks * chunk, rank))
            slot += chunks * chunk * length
            row += chunks * chunk
        if cut:

            def one_piece(i, slot=slot):
                at = slot + i * piece
                with phase("als.fetch"):
                    ids = jax.lax.dynamic_slice(idx, (at,), (piece,))
                    r = jax.lax.dynamic_slice(val, (at,), (piece,))
                return systems(ids[None], r[None], fixed, alpha)[0]

            parts = jax.lax.map(one_piece, jnp.arange(pieces, dtype=jnp.int32))
            # The padding's pieces name the target ``cut``: dropped.
            whole = jax.ops.segment_sum(parts, owner[:pieces], num_segments=cut)
            out.append(solved(whole, jax.lax.dynamic_slice(counts, (row,), (cut,)),
                              shared, reg))
        local = jnp.pad(jnp.concatenate(out), ((0, 0), (0, LANES - rank)))
        everyone = jax.lax.all_gather(local, axis, tiled=True)
        everyone = jnp.concatenate([everyone, jnp.zeros((1, LANES), f32)])
        table = everyone.at[where].get(mode="promise_in_bounds")
        return (jnp.concatenate([table, jnp.zeros((1, LANES), f32)]),
                table[:, :rank])

    return half_step


@functools.lru_cache(maxsize=32)
def _program(mesh, plan: Tuple, rank: int, implicit: bool, precision, on_lanes: bool):
    """The program ``als_half_step`` of one side's plan on ``mesh``."""
    axis = DeviceMesh.DATA_AXIS
    fn = make_half_step(plan, rank, implicit, axis, precision, on_lanes)
    return jax.jit(jax.shard_map(
        named_program("als_half_step", fn, phases=PHASES), mesh=mesh,
        in_specs=(P(axis),) * 4 + (P(),) * 4, out_specs=(P(), P()),
        # The outputs are made of the all-gathered rows, the same on every
        # device: what the replication check cannot see of an all_gather.
        check_vma=False))


def start_factors(seed: int, items: int, rank: int) -> jax.Array:
    """The item factors a fit starts from: ``N(0, 1 / sqrt(rank))``
    ``[items, rank]`` float32 from the seed, made on the device."""
    return jax.random.normal(
        jax.random.PRNGKey(seed), (items, rank), jnp.float32) / np.float32(np.sqrt(rank))


def fit_table(est, table, precision=GRAM_PRECISION):
    """``ALS.fit`` of a :class:`Table`: ``(user_ids, user_factors
    [users, rank], item_ids, item_factors [items, rank])``, the factors
    float32 host arrays as the chip returned them. The caller's span
    ``fit`` holds all of it. ``precision`` is the benchmark's control's
    alone."""
    cols = [est.get(c) for c in (est.USER_COL, est.ITEM_COL, est.RATING_COL)]
    if table.num_rows == 0:
        raise ValueError("training table is empty")
    implicit = bool(est.get(est.IMPLICIT_PREFS))
    if implicit and float(np.min(table.column(cols[2]))) < 0:
        raise ValueError("implicitPrefs requires non-negative ratings")
    mesh = est.mesh or DeviceMesh()
    placed = table.device_resident(
        ("als_orders_on_mesh", *cols, mesh.mesh, "float32"),
        lambda make_room: place(*(np.asarray(table.column(c)) for c in cols),
                                mesh, make_room))
    rank, max_iter = est.get(est.RANK), est.get(est.MAX_ITER)
    on_lanes = not _gate.interpret_mode()
    with jax.enable_x64(False):
        with span("als.init"):
            start = start_factors(est.get_seed(), placed.item_ids.size, rank)
            item_table = mesh.replicate(jnp.pad(start, ((0, 1), (0, LANES - rank))))
        reg, alpha = np.float32(est.get(est.REG_PARAM)), np.float32(est.get(est.ALPHA))
        users_from, items_from = (
            functools.partial(
                _program(mesh.mesh, side.plan, rank, implicit, precision, on_lanes),
                *side[:5]) for side in (placed.by_user, placed.by_item))
        with span("als.loop"):
            with span("als.dispatch"):
                for _ in range(max_iter):
                    user_table, user_rows = users_from(item_table, reg, alpha)
                    item_table, item_rows = items_from(user_table, reg, alpha)
            # The caller reads the factors next: waiting here costs nothing
            # and gives the loop a span its device time lies in.
            jax.block_until_ready((user_rows, item_rows))
        with span("als.readback"):
            user_rows, item_rows = np.asarray(user_rows), np.asarray(item_rows)
    counters = metrics.group("als")
    counters.counter("fits")
    counters.counter("half_steps", 2.0 * max_iter)
    counters.counter("ratings", 2.0 * max_iter * placed.ratings)
    slots = max_iter * (placed.by_user.slots + placed.by_item.slots)
    counters.counter("rating_slots", float(slots))
    counters.counter("padding_slots", float(slots - 2 * max_iter * placed.ratings))
    counters.counter("targets",
                     float(max_iter * (placed.by_user.targets + placed.by_item.targets)))
    return placed.user_ids, user_rows, placed.item_ids, item_rows
