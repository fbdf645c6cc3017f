"""Pallas blocked sparse step — the two-level one-hot lookup of a batch's
cells in their blocks, and its transpose, in fast memory.

``ops.sparse.block_lookup`` and ``block_accumulate`` are these kernels'
reference and what every backend but a TPU runs. Through XLA each slot's
one-hot product ``rows [batch, 128]`` float32 (33.5 MB at 65,536 rows)
goes to HBM and comes back for ONE float of each 128 to be kept: 28
slots of ``lr-criteo``'s 39 do that twice a step, and the step runs at
the HBM rate on bytes the algorithm does not need. Here a tile of the
batch (up to :data:`TILE` rows, along the LANES) meets every slot in
turn and a slot's product is made, selected from and dropped in VMEM.

Everything is laid with the batch along the lanes, as the chip holds an
ELL table: ``cells [width, batch]`` and ``vals [width, batch]`` are the
window's cells a slot a row, as they are (which rows the plan's blocked
slots are, and the row of 128 columns each one's block starts at, are
two small tables in SMEM); a slot's one-hot is ``[n, tile]``, the cell's
index against an iota down the sublanes (a sublane broadcast, no lane
shuffle), and picking one of ``n`` looked-up rows is the same kind of
mask and a sum down the sublanes. A block of ``length`` columns is ``r``
product rows of ``c`` columns (:func:`shape`; ``local = c * hi + lo``).

*The lookup stays the gather bit for bit*, in two forms, by what a block's
:func:`shape` is. A *narrow* block (``r`` ≤ 32: up to 4,096 columns) is
three bfloat16 parts: a float32 is exactly three of them, a 0/1 operand
is exact in bfloat16, the MXU sums in float32, and the three parts added
as they lie (``(hi + mid) + lo``, or from the other end) pass through
float32 values only. The parts lie along the contraction against the
one-hot of ``hi`` repeated, so the contraction itself adds them: ONE
pass of ``[c, 128] @ [128, tile]``; up to 256 columns ``c`` is 8, so
that ``lo`` picks among 8 rows and not 128. A narrow slot is bound by
its weight tile's load, not by passes, and place values would not
survive being summed by the contraction, so it keeps its parts. A *wide*
block contracts ``lo`` (128 lanes: a whole MXU tile, nothing padded)
while its rows stream through, and there the passes are the cost: a
lookup SELECTS and does no arithmetic on the float, so a wide block's
operand is the four int8 digits of each float's BITS (``_split.digits``,
``kernels.payload_blocks``' form since PR 53), ``[4 rows, 128] @ [128,
tile]`` int8 by int8 into int32 at the MXU's int8 rate, twice
bfloat16's; ``hi`` picks each digit plane's row and shifts and adds put
the four picked rows together into the float's bits, whatever they are
(PR 58: a 26,624-column slot 0.059 → 0.041 ms on a v5e, the three
bfloat16 passes having stood at 87 % of the MXU's own time).

*The accumulation* is the transpose, one form for both (it ADDS floats,
and sums cannot travel as digits): the cells' contributions ``vals ×
mult`` in three bfloat16 parts (so the products
are exact) on their product rows, ``[3 rows, tile]``, contracted over
the tile with the 0/1 mask of ``lo``, ``[c, tile]``; summed in float32
into an output that stays in VMEM over the grid's one axis, which is
sequential: one fixed order, no atomics, the same bits every run.

The slots of one :func:`shape` are a ``fori_loop`` over a stacked
operand and the shapes are few: four bodies for ``lr-criteo``'s 39 slots
on eleven block lengths. A process traces and lowers the kernels at its
first fit whatever the compile cache holds, so what is traced is kept
small. Traced in 32-bit mode whatever the caller's (PR 30).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence, Tuple

from flinkml_tpu.kernels._split import digits, joined_digits, rounded_parts

#: Lanes of a vreg, and the columns of a block's row as the trainer
#: holds it (``[length / 128, 128]``).
LANES = 128
#: Sublanes of a float32 vreg.
SUBLANES = 8
#: Sublanes of a bfloat16 vreg: one-hots and parts come in whole tiles.
PACKED = 16
#: Batch rows a grid step holds (the most; :func:`tile_rows`).
TILE = 4096
#: Most product rows of a narrow block: three copies of them, each up to
#: whole bfloat16 tiles, are one contraction of at most 128.
NARROW_ROWS = 32
#: Fast memory the kernels may use (a v5e has 128 MiB, the compiler's
#: own limit is 16), and what of it a grid step's values, or the
#: operands that stay for the whole grid, may take.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_STEP_BYTES = 24 * 1024 * 1024
#: Bytes a grid step holds for each product row of a wide block and batch
#: row: the lookup's four digit planes in int32, the row's mask and a
#: plane picked from (the accumulation's three parts' product in float32,
#: their sum and its masks are 24).
_LIVE_BYTES = 28
#: Bytes that stay in fast memory for each block column, in two buffers:
#: a narrow block's three bfloat16 parts (a wide block's four int8
#: digits, and either's float32 sums, are 8).
_RESIDENT_BYTES = 12


class Group(NamedTuple):
    """Consecutive slots of the walk whose blocks have one shape in the
    kernels: one loop, one traced body (a process traces and lowers the
    kernels at its first fit whatever the compile cache holds, so the
    shapes are few)."""
    members: Tuple[Tuple[int, int], ...]   # (block length, slots), in turn
    first: int      # where the slots start in the walk (``where``)
    c: int          # columns a product row
    rows: int       # product rows, whole bfloat16 tiles
    narrow: bool    # parts along the contraction (one pass)

    @property
    def slots(self) -> int:
        return sum(slots for _, slots in self.members)


def shape(length: int) -> Tuple[int, int, bool]:
    """``(c, rows, narrow)`` for a block of ``length`` columns, ``length
    / c`` product rows of ``c`` columns in room for ``rows``. Narrow, at
    most :data:`NARROW_ROWS` rows: of 8 columns up to 256 columns, of 128
    up to 4,096. Wide: its rows of 128 as the trainer holds them, in
    room for 128 or, longer, whole bfloat16 tiles (of 16: the
    accumulation's parts; four digit planes of them are whole int8
    tiles of 32)."""
    if length <= SUBLANES * NARROW_ROWS:
        return SUBLANES, NARROW_ROWS, True
    if length <= LANES * NARROW_ROWS:
        return LANES, NARROW_ROWS, True
    return LANES, max(LANES, -(-length // LANES // PACKED) * PACKED), False


def walk(groups: Sequence[Tuple[int, int]]) -> Tuple[Group, ...]:
    """``[(length, slots)]`` (``ops.sparse.block_groups``' lengths and
    counts, in its order) as the kernels' :class:`Group`s: neighbours of
    one :func:`shape` together."""
    out, first = [], 0
    for length, slots in groups:
        if length % LANES or slots < 1:
            raise ValueError(f"a block is whole rows of {LANES}: {length}")
        if out and out[-1][2:] == shape(length):
            out[-1] = out[-1]._replace(members=out[-1].members + ((length, slots),))
        else:
            out.append(Group(((length, slots),), first, *shape(length)))
        first += slots
    return tuple(out)


def tile_rows(batch: int, groups: Sequence[Group]) -> Optional[int]:
    """Batch rows a grid step: the most, of :data:`TILE` halved down to
    128, that divide the batch and keep what a step makes of the longest
    block (its digits' product and a plane picked from, or its parts'
    product and their sum: about :data:`_LIVE_BYTES` a product row and
    batch row) inside
    :data:`_STEP_BYTES`; None where none does (the caller keeps XLA's
    products)."""
    rows = max(g.rows for g in groups)
    tile = TILE
    while tile >= LANES:
        if batch % tile == 0 and _LIVE_BYTES * rows * tile <= _STEP_BYTES:
            return tile
        tile //= 2
    return None


def unsupported_reason(dtype, batch: int,
                       groups: Sequence[Tuple[int, int]]) -> Optional[str]:
    """Why the kernels do not take this step (None = they do)."""
    import jax.numpy as jnp

    if jnp.dtype(dtype) != jnp.float32:
        return f"coefficients {dtype}: the parts are a float32's"
    columns = sum(length * slots for length, slots in groups)
    if _RESIDENT_BYTES * columns > _STEP_BYTES:
        return (f"{columns} block columns: their parts or digits, or their "
                "sums, would not stay in fast memory")
    if tile_rows(batch, walk(groups)) is None:
        return f"a batch of {batch} rows a device is not whole tiles of {LANES}"
    return None


def block_parts(blocks, group: Group):
    """A group's blocks (each member's ``[slots, length / 128, 128]``
    float32) as the lookup's left operand ``[slots, .., ..]``. A narrow
    block's is ``[c, 128]`` bfloat16: column ``lo`` of product row ``hi``
    at ``[lo, p * rows + hi]`` for part ``p``, zeros between and after
    (``[c, 3 rows] @ [3 rows, tile]``). A wide block's is its rows of
    128 as they are, int8: the four digits of the floats' bits
    (``_split.digits``) one under the other at multiples of ``rows``,
    ``[4 rows, 128]``. Made once a step by XLA, under a megabyte in all
    (the parts' roundings are ``lax.reduce_precision`` there, because
    inside one fusion XLA keeps a value it has just rounded at float32;
    PERF.md section 6, PR 35)."""
    import jax.numpy as jnp

    padded = []
    for (length, slots), member in zip(group.members, blocks):
        r = length // group.c
        if group.narrow:
            # [slots, c, r]: product row r of column c.
            member = member.reshape(slots, r, group.c).transpose(0, 2, 1)
            pad = ((0, 0), (0, 0), (0, group.rows - r))
        else:
            pad = ((0, 0), (0, group.rows - r), (0, 0))
        padded.append(jnp.pad(member, pad))
    padded = jnp.concatenate(padded)
    if not group.narrow:
        return jnp.concatenate(digits(padded), axis=1)
    stacked = jnp.concatenate(rounded_parts(padded, in_kernel=False), axis=2)
    return jnp.pad(stacked, ((0, 0), (0, 0), (0, LANES - 3 * group.rows)))


def _cells_of(at, where_ref, starts_ref, cells_ref, vals_ref):
    """The ``at``-th of the walked slots: its cells' columns from their
    block's first, and their values, ``[1, tile]`` each. ``where_ref``
    (SMEM) says which row of ``cells_ref`` / ``vals_ref`` it is,
    ``starts_ref`` (SMEM) at which row of 128 columns its block starts."""
    from jax.experimental import pallas as pl

    slot = where_ref[at]
    local = cells_ref[pl.ds(slot, 1), :] - LANES * starts_ref[slot]
    return local, vals_ref[pl.ds(slot, 1), :]


def _one_hot(index, n: int):
    """``[n, tile]`` bool: sublane ``i`` of lane ``t`` is ``index[0, t]
    == i``. An index outside ``[0, n)`` matches nothing."""
    import jax
    import jax.numpy as jnp

    shape = (n, index.shape[1])
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0) == jnp.broadcast_to(
        index, shape)


def _as_operand(mask):
    """A mask as the 0/1 bfloat16 operand of a product."""
    import jax.numpy as jnp

    return jnp.where(mask, 1.0, 0.0).astype(jnp.bfloat16)


def _sum_of_parts(three, rows: int):
    """``[3 rows, n]``, a product's three parts one under the other,
    summed as they lie: each partial sum is a float32."""
    return (three[:rows] + three[rows:2 * rows]) + three[2 * rows:]


def _down_to_a_vreg(x):
    """``[n, tile]`` summed down its sublanes to ``[8, tile]``: vreg
    adds, no shuffle."""
    n, tile = x.shape
    if n == SUBLANES:
        return x
    return x.reshape(n // SUBLANES, SUBLANES, tile).sum(axis=0)


def _split(local, group: Group):
    """``(hi, lo)`` of ``local = c * hi + lo``."""
    return local >> (group.c.bit_length() - 1), local & (group.c - 1)


def _picked_narrow(parts, hi, lo, group: Group):
    """A narrow block's looked-up floats, ``[8, tile]`` float32 (a lane's
    one sublane not 0): the parts along the contraction, the one-hot of
    ``hi`` under each, and ``lo`` picks among the ``c`` rows."""
    import jax.numpy as jnp

    tile = hi.shape[1]
    rows_of = _as_operand(_one_hot(hi, group.rows))
    rest = LANES - 3 * group.rows
    stacked = [rows_of] * 3 + (
        [jnp.zeros((rest, tile), jnp.bfloat16)] if rest else [])
    looked = jnp.dot(parts, jnp.concatenate(stacked, axis=0),
                     preferred_element_type=jnp.float32)
    return _down_to_a_vreg(jnp.where(_one_hot(lo, group.c), looked, 0.0))


def _picked_wide(four_digits, hi, lo, rows: int):
    """A wide block's looked-up floats, ``[8, tile]`` float32 (a lane's
    one sublane not 0): the lane is contracted (128: a whole MXU tile),
    the digits' rows stream through it at int8's rate, ``hi`` picks each
    plane's row and the four picked rows are put together, all in
    integers. Picked BEFORE they are joined: each plane of the product
    is selected from and summed down as it comes, and no ``[rows, tile]``
    value but the product is kept (joined first, a 26,624-column slot
    read 0.054 ms for 0.041 on a v5e; PERF.md section 6, PR 58)."""
    import jax
    import jax.numpy as jnp

    lanes_of = jnp.where(_one_hot(lo, LANES), 1, 0).astype(jnp.int8)
    four = jnp.dot(four_digits, lanes_of, preferred_element_type=jnp.int32)
    rows_of = _one_hot(hi, rows)
    return jax.lax.bitcast_convert_type(joined_digits([
        _down_to_a_vreg(jnp.where(rows_of, four[k * rows:(k + 1) * rows], 0))
        for k in range(4)]), jnp.float32)


def _lookup_body(where_ref, starts_ref, cells_ref, vals_ref, *refs, groups):
    import jax
    import jax.numpy as jnp

    *block_refs, out_ref = refs
    tile = out_ref.shape[1]
    acc = jnp.zeros((SUBLANES, tile), jnp.float32)
    for group, block_ref in zip(groups, block_refs):

        def one_slot(i, acc, group=group, block_ref=block_ref):
            local, vals = _cells_of(group.first + i, where_ref, starts_ref,
                                    cells_ref, vals_ref)
            hi, lo = _split(local, group)
            if group.narrow:
                picked = _picked_narrow(block_ref[i], hi, lo, group)
            else:
                picked = _picked_wide(block_ref[i], hi, lo, group.rows)
            return acc + picked * jnp.broadcast_to(vals, (SUBLANES, tile))

        acc = jax.lax.fori_loop(0, group.slots, one_slot, acc)
    out_ref[...] = jnp.sum(acc, axis=0, keepdims=True)


def _accumulate_body(where_ref, starts_ref, cells_ref, vals_ref, mult_ref,
                     *out_refs, groups):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _():
        for out_ref in out_refs:
            out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)

    tile = mult_ref.shape[1]
    mult = mult_ref[...]
    for group, out_ref in zip(groups, out_refs):

        def one_slot(i, carry, group=group, out_ref=out_ref):
            rows = group.rows
            local, vals = _cells_of(group.first + i, where_ref, starts_ref,
                                    cells_ref, vals_ref)
            hi, lo = _split(local, group)
            # The contributions' three parts on the cells' product rows,
            # one under the other (each a bfloat16's value, selected in
            # float32 by the 32-bit mask and then packed); the columns'
            # mask is 0/1; the tile is contracted.
            rows_of = _one_hot(hi, rows)
            spread = jnp.concatenate(
                [jnp.where(rows_of, jnp.broadcast_to(
                    part.astype(jnp.float32), (rows, tile)), 0.0)
                 .astype(jnp.bfloat16)
                 for part in rounded_parts(vals * mult, in_kernel=True)],
                axis=0)
            three = jax.lax.dot_general(
                spread, _as_operand(_one_hot(lo, group.c)),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            out_ref[i] += _sum_of_parts(three, rows)
            return carry

        jax.lax.fori_loop(0, group.slots, one_slot, 0)


def _call(body, groups, tile: int, where, starts, operands, in_specs,
          out_specs, out_shape, semantics: str, interpret):
    """One kernel over the batch's tiles: ``where`` (the walked slots'
    rows of the cells) and ``starts`` go to SMEM before the grid runs."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from flinkml_tpu.kernels import _mosaic

    if interpret is None:
        interpret = _mosaic.interpret_mode()
    return pl.pallas_call(
        functools.partial(body, groups=groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(operands[0].shape[1] // tile,),
            in_specs=in_specs, out_specs=out_specs),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(semantics,),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.asarray(where, jnp.int32), starts, *operands)


def _tiles(rows: int, tile: int):
    """``[rows, batch]`` a tile of the batch at a time."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec((rows, tile), lambda t, *_: (0, t))


def _whole(shape):
    from jax.experimental import pallas as pl

    return pl.BlockSpec(shape, lambda t, *_: (0,) * len(shape))


def lookup_dot(groups: Sequence[Tuple[int, int]], where: Sequence[int], blocks,
               cells, vals, starts, *, interpret: Optional[bool] = None):
    """``sum_s vals[s, b] * blocks_s[cells[s, b] - 128 * starts[s]]``
    over the walked slots ``s``, ``[batch]`` float32: the blocked slots'
    share of the margin. ``groups`` ``[(length, slots)]`` and ``where``
    the rows of ``cells [width, batch]`` int32 (a step's cells, a slot a
    row: their columns) and ``vals [width, batch]`` float32 that are the
    groups' slots in turn (rows not named are not read); ``blocks`` a
    group's ``[slots, length / 128, 128]`` float32 each; ``starts
    [width]`` int32 the row of 128 columns each slot's block starts at.
    Each looked-up float is the block's bit for bit; a cell outside its
    block reads 0 or some float of it and must carry the value 0.
    ``batch`` is whole tiles (:func:`tile_rows`)."""
    import jax
    import jax.numpy as jnp

    from flinkml_tpu.kernels import _mosaic

    groups = walk(groups)
    width, batch = cells.shape
    tile = tile_rows(batch, groups)
    blocks = iter(blocks)
    with jax.enable_x64(False):
        operands = [block_parts([next(blocks) for _ in g.members], g)
                    for g in groups]
        out = _call(
            _lookup_body, groups, tile, where, starts, [cells, vals] + operands,
            [_tiles(width, tile)] * 2 + [_whole(o.shape) for o in operands],
            _tiles(1, tile),
            _mosaic.out_struct((1, batch), jnp.float32, cells, vals, starts,
                             *operands),
            "parallel", interpret)
    return out[0]


def accumulate(groups: Sequence[Tuple[int, int]], where: Sequence[int], cells,
               vals, starts, mult, *, interpret: Optional[bool] = None):
    """Each group's ``zeros([slots, length]).at[s, cells[s, b] - 128 *
    starts[s]].add(vals[s, b] * mult[b])`` as ``[slots, length / 128,
    128]`` float32 (one of each ``(length, slots)`` of ``groups``), the
    operands :func:`lookup_dot`'s: its transpose.
    The products exact, the sums float32 in one fixed order (a tile's
    cells on the MXU, the tiles in turn): the same bits every run. A
    cell outside its block must contribute 0."""
    import jax
    import jax.numpy as jnp

    from flinkml_tpu.kernels import _mosaic

    groups = walk(groups)
    width, batch = cells.shape
    tile = tile_rows(batch, groups)
    shapes = [(g.slots, g.rows, g.c) for g in groups]
    with jax.enable_x64(False):
        sums = _call(
            _accumulate_body, groups, tile, where, starts,
            [cells, vals, mult[None, :]],
            [_tiles(width, tile)] * 2 + [_tiles(1, tile)],
            [_whole(s) for s in shapes],
            [_mosaic.out_struct(s, jnp.float32, cells, vals, starts, mult)
             for s in shapes],
            "arbitrary", interpret)
    out = []
    for group_sums, g in zip(sums, groups):
        at = 0
        for length, slots in g.members:
            out.append(group_sums[at:at + slots, :length // g.c].reshape(
                slots, length // LANES, LANES))
            at += slots
    return out
