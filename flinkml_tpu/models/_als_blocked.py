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
- **A chunk** (``systems``): the slots' fixed-side rows ``Y [c, L,
  128]`` fetched (rank padded to 128 lanes, lane ``k`` free; XLA's gather,
  or, where the fixed side's heaviest rows cover enough of the slots,
  ``kernels.row_fetch``: those rows read out of fast memory and the gather
  kept for the cold slots alone, :func:`_lay_fetch`), ONE batched product
  ``Y' [a Y | b]`` at :data:`GRAM_PRECISION` giving ``A``
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
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from flinkml_tpu.kernels import _mosaic, row_fetch, spd_solve
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
                          # (with ``fetch``: row_fetch's local indices)
    val: jax.Array        # [p * slots_local] float32 ratings, 0 at padding
    counts: jax.Array     # [p * rows_local] float32 ratings a local target
    owner: jax.Array      # [p * pieces] int32: a piece's local cut target
    where: jax.Array      # [targets] int32: a target's row of the gathered solved rows
    plan: Tuple           # ((length, chunk, chunks), ...), (pieces, cut targets), slots a piece
    targets: int
    slots: int            # all devices', padding included
    # Where kernels.row_fetch fetches the rows (:func:`_lay_fetch`): the
    # devices' cold ids, where a turn's begin and the tiles' starts, the hot
    # rows' ids; its static plan (hot rows, a tile's DMA); and the slots that
    # name a hot row.
    fetch: Tuple = ()
    fetch_plan: Optional[Tuple] = None
    hot_slots: int = 0

    @property
    def operands(self) -> Tuple:
        """What the side's program takes before the fixed side."""
        return (*self[:5], *self.fetch)


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


def _loops(plan: Tuple):
    """``(slots a turn, turns)`` of a device's loops in slot order: every
    bucket's chunks, then the cut targets' pieces."""
    buckets, (pieces, _), piece = plan
    return ([(length * chunk, chunks) for length, chunk, chunks in buckets]
            + ([(piece, pieces)] if pieces else []))


#: Slots a thread lays at a time in :func:`_lay_fetch`.
_LAY_SLOTS = 1 << 21


def hot_rows_of(degrees: np.ndarray, slots: int):
    """The fixed side's hot rows where ``kernels.row_fetch`` fetches a
    side's ``slots`` slots, None where XLA's gather does
    (``row_fetch.unsupported_reason``: the backend, and the share of the
    slots that name a hot row): ``(ids [hot] int32, cold slots)``, the
    rows with the most ratings and, last, the zero row (which every
    padding slot names; it fills a short table's list), and the slots
    that name any other row. ``degrees`` are the ratings of the fixed
    side's rows, the zero row's not among them."""
    rows = degrees.size
    hot = row_fetch.hot_rows(rows + 1)
    heavy = (np.arange(rows) if rows < hot
             else np.argpartition(degrees, rows - (hot - 1))[rows - (hot - 1):])
    ids = np.full(hot, rows, np.int32)
    ids[:heavy.size] = heavy
    cold = int(degrees.sum()) - int(degrees[heavy].sum())
    if row_fetch.unsupported_reason(np.float32, LANES, 1.0 - cold / max(1, slots)):
        return None
    return ids, cold


def _lay_fetch(other: np.ndarray, slots: np.ndarray, plan: Tuple, hot_ids: np.ndarray,
               p: int):
    """The devices' slots as ``kernels.row_fetch`` reads them, a turn of a
    loop a call, from ``other`` (the fixed side's column, the zero row's
    behind it) and ``slots`` (:func:`_slot_order`): ``(loc [p *
    slots_local], cold [p * cold ids a device], cold_at [p * (turns a
    device + 1)], starts [p * tiles a device], (hot rows, a tile's
    DMA))``: a turn's cold ids are ``cold[cold_at[turn]:cold_at[turn +
    1]]`` of its device's, whole blocks. No shape but the cold ids'
    total (in steps of a sixteenth or so of itself) and the DMA's rows (a
    power of two) follows the table's ids, so a program compiled for one
    table serves another of the same plan. The gather pool's threads take
    :data:`_LAY_SLOTS` slots at a time: gathered and laid while in cache."""
    loops = _loops(plan)
    rank = row_fetch.ranks(hot_ids, int(other[-1]) + 1)
    loc = np.empty(slots.size, np.int32)
    jobs = []                               # (device, first slot, turns, slots a turn)
    for device in range(p):
        at = device * (slots.size // p)
        for n, turns in loops:
            each = max(1, _LAY_SLOTS // n)
            jobs += [(device, at + lo * n, min(each, turns - lo), n)
                     for lo in range(0, turns, each)]
            at += turns * n

    def lay(job):
        _, at, turns, n = job
        ids = other.take(slots[at:at + turns * n], mode="clip").reshape(turns, n)
        return row_fetch.localize(ids, rank, hot_ids.size,
                                  loc[at:at + turns * n].reshape(turns, n))

    with gather_pool() as pool:
        laid = list(pool.map(lay, jobs))
    by_device = [[local for job, local in zip(jobs, laid) if job[0] == device]
                 for device in range(p)]
    lengths = [np.concatenate([local.lengths for local in mine]) for mine in by_device]
    most = max(row_fetch.BLOCK, max(int(own.sum()) for own in lengths))
    step = max(row_fetch.BLOCK, 1 << (most.bit_length() - 4))   # a sixteenth or so
    held = -(-most // step) * step
    cold = np.zeros((p, held), np.int32)
    for device, mine in enumerate(by_device):
        listed = np.concatenate([local.cold for local in mine])
        cold[device, :listed.size] = listed
    cold_at = np.stack([np.concatenate([[0], np.cumsum(own)]) for own in lengths])
    starts = np.concatenate([local.starts.reshape(-1) for local in laid])
    run = max(local.run for local in laid)
    cap = max(row_fetch.GROUP, 1 << (run - 1).bit_length())
    return (loc, cold.reshape(-1), cold_at.astype(np.int32).reshape(-1), starts,
            (hot_ids.size, cap))


def _place_side(order: _Order, other: np.ndarray, ratings: np.ndarray,
                plan: _Plan, mesh: DeviceMesh, hot: Optional[Tuple] = None):
    """One order on the mesh: ``other`` (the fixed side's positions, the
    zero row's behind them) and ``ratings`` (a 0 behind them) gathered
    into the slots and sent through :meth:`DeviceMesh.stage_rows`. With
    ``hot`` (:func:`hot_rows_of`) the slots hold ``kernels.row_fetch``'s
    local indices, and the cold slots' ids lie beside them."""
    p = mesh.axis_size()
    slots = _slot_order(order, plan, p, other.size - 1)
    fetch, fetch_plan, hot_slots = (), None, 0
    if hot is not None:
        hot_ids, cold_slots = hot
        loc, cold, cold_at, starts, fetch_plan = _lay_fetch(
            other, slots, plan.plan, hot_ids, p)
        *_, ((val,), _) = mesh.stage_rows([(ratings, slots, np.float32)])
        idx = mesh.shard_batch(loc)
        fetch = (mesh.shard_batch(cold), mesh.shard_batch(cold_at),
                 mesh.shard_batch(starts), mesh.replicate(hot_ids))
        hot_slots = slots.size - cold_slots
    else:
        *_, ((idx, val), _) = mesh.stage_rows(
            [(other, slots, np.int32), (ratings, slots, np.float32)])
    degrees = np.diff(order.indptr)
    held = degrees > 0
    counts = np.zeros((p, plan.rows_local), np.float32)
    counts[plan.device[held], plan.row[held]] = degrees[held]
    where = (plan.device * plan.rows_local + plan.row).astype(np.int32)
    return _Side(idx, val, mesh.shard_batch(counts.reshape(-1)),
                 mesh.shard_batch(plan.owner.reshape(-1)), mesh.replicate(where),
                 plan.plan, degrees.size, p * plan.slots_local,
                 fetch, fetch_plan, hot_slots)


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
        degrees = np.diff(by_user.indptr), np.diff(by_item.indptr)
        plans = plan_side(degrees[0], p, piece), plan_side(degrees[1], p, piece)
        # A side's fixed side is the other side's targets.
        hot = (hot_rows_of(degrees[1], p * plans[0].slots_local),
               hot_rows_of(degrees[0], p * plans[1].slots_local))
        u, i = _with_tail(u, user_ids.size, np.int32), _with_tail(i, item_ids.size, np.int32)
        r = _with_tail(np.asarray(ratings), 0, np.float32)
    nbytes = sum(p * (8 * plan.slots_local + 4 * plan.rows_local + 4 * plan.owner.shape[1])
                 + 4 * plan.row.size for plan in plans)
    # Under row_fetch the cold slots' ids come on top: four bytes each, and
    # a quarter more for the padding to whole groups, blocks and steps.
    make_room(nbytes + sum(5 * side[1] for side in hot if side is not None), devices)
    with span("als.table_to_device") as phase, ThreadPoolExecutor(2) as both:
        # The two orders side by side: a side's passes are NumPy's on the
        # gather pool's threads and leave cores idle between them.
        sides = tuple(both.map(
            lambda side: _place_side(*side[:3], side[3], mesh, side[4]),
            ((by_user, i, r, plans[0], hot[0]), (by_item, u, r, plans[1], hot[1]))))
        nbytes += sum(x.nbytes for side in sides for x in side.fetch)
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
                   precision=GRAM_PRECISION, on_lanes: bool = False,
                   fetch_plan: Optional[Tuple] = None):
    """One device's half-step under ``plan`` (:func:`plan_side`):
    ``(idx, val, counts, owner, where, fixed [n + 1, 128], reg, alpha) ->
    (table [targets + 1, 128], rows [targets, rank])``, the solved
    factors of ALL targets (every device's, gathered), as the next
    half-step's fixed side (lanes padded, the zero row last) and as the
    model holds them. Under a ``fetch_plan`` (:func:`_lay_fetch`) ``idx``
    holds ``kernels.row_fetch``'s local indices and ``cold, cold_at,
    starts, hot_ids`` come before ``fixed``."""
    buckets, (pieces, cut), piece = plan
    width = spd_solve.augmented_width(rank)
    f32 = jnp.float32

    def systems(y, r, alpha):
        """``[c, rank, width]``: ``A`` and ``b`` of the ``c`` targets
        whose slots' fixed-side rows ``y [c, L, 128]`` and ratings ``r
        [c, L]`` are, unregularised."""
        with phase("als.fetch"):
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

    def half_step(idx, val, counts, owner, where, *rest):
        *fetch, fixed, reg, alpha = rest
        if implicit:
            shared = jnp.einsum("nk,nm->km", fixed, fixed,
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=f32)[:rank, :width]
        else:
            shared = jnp.zeros((rank, width), f32)
        if fetch_plan is not None:
            cold, cold_at, starts, hot_ids = fetch
            _, cap = fetch_plan
            with phase("als.fetch"):
                hot = fixed.at[hot_ids].get(mode="promise_in_bounds")

        def fetched(rows, i, slot, n, turn, tile_at):
            """Turn ``i`` of a loop of ``n`` slots a turn from ``slot``: their
            fixed-side rows ``[n, 128]`` and ratings ``[n]``. Under a fetch
            plan ``rows`` is the loop's buffer of cold rows, handed on."""
            with phase("als.fetch"):
                ids = jax.lax.dynamic_slice(idx, (slot + i * n,), (n,))
                r = jax.lax.dynamic_slice(val, (slot + i * n,), (n,))
                if fetch_plan is None:
                    return rows, fixed.at[ids].get(mode="promise_in_bounds"), r
                tiles = row_fetch.tiles_of(n)
                first, end = cold_at[turn + i], cold_at[turn + i + 1]
                rows = row_fetch.fetch_cold(
                    fixed, cold, first, (end - first) // row_fetch.BLOCK, rows)
                return rows, row_fetch.fetch(
                    ids, jax.lax.dynamic_slice(starts, (tile_at + i * tiles,), (tiles,)),
                    hot, rows, cap=cap), r

        def turns_of(n, turns, one_turn):
            """``one_turn(rows, i) -> (rows, out)`` over a loop's turns."""
            rows = (() if fetch_plan is None
                    else jnp.zeros((row_fetch.cold_rows(n, cap), LANES), f32))
            return jax.lax.scan(one_turn, rows, jnp.arange(turns, dtype=jnp.int32))[1]

        out, slot, row, turn, tile_at = [], 0, 0, 0, 0
        for length, chunk, chunks in buckets:

            def one_chunk(rows, i, at=(slot, chunk * length, turn, tile_at),
                          row=row, length=length, chunk=chunk):
                rows, y, r = fetched(rows, i, *at)
                g = systems(y.reshape(chunk, length, LANES), r.reshape(chunk, length),
                            alpha)
                n = jax.lax.dynamic_slice(counts, (row + i * chunk,), (chunk,))
                return rows, solved(g, n, shared, reg)

            out.append(turns_of(chunk * length, chunks, one_chunk)
                       .reshape(chunks * chunk, rank))
            slot += chunks * chunk * length
            row += chunks * chunk
            turn += chunks
            tile_at += chunks * row_fetch.tiles_of(chunk * length)
        if cut:

            def one_piece(rows, i, at=(slot, piece, turn, tile_at)):
                rows, y, r = fetched(rows, i, *at)
                return rows, systems(y[None], r[None], alpha)[0]

            parts = turns_of(piece, pieces, one_piece)
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
def _program(mesh, plan: Tuple, rank: int, implicit: bool, precision, on_lanes: bool,
             fetch_plan: Optional[Tuple] = None):
    """The program ``als_half_step`` of one side's plan on ``mesh``."""
    axis = DeviceMesh.DATA_AXIS
    fn = make_half_step(plan, rank, implicit, axis, precision, on_lanes, fetch_plan)
    fetch_specs = () if fetch_plan is None else (P(axis), P(axis), P(axis), P())
    return jax.jit(jax.shard_map(
        named_program("als_half_step", fn, phases=PHASES), mesh=mesh,
        in_specs=(P(axis),) * 4 + (P(),) + fetch_specs + (P(),) * 3, out_specs=(P(), P()),
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
    on_lanes = not _mosaic.interpret_mode()
    with jax.enable_x64(False):
        with span("als.init"):
            start = start_factors(est.get_seed(), placed.item_ids.size, rank)
            item_table = mesh.replicate(jnp.pad(start, ((0, 1), (0, LANES - rank))))
        reg, alpha = np.float32(est.get(est.REG_PARAM)), np.float32(est.get(est.ALPHA))
        users_from, items_from = (
            functools.partial(
                _program(mesh.mesh, side.plan, rank, implicit, precision, on_lanes,
                         side.fetch_plan),
                *side.operands) for side in (placed.by_user, placed.by_item))
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
    counters.counter("hot_slots",
                     float(max_iter * (placed.by_user.hot_slots + placed.by_item.hot_slots)))
    counters.counter("targets",
                     float(max_iter * (placed.by_user.targets + placed.by_item.targets)))
    return placed.user_ids, user_rows, placed.item_ids, item_rows
