"""Pallas blocked payload walk — a factorization machine's rows looked up
in their blocks, and their gradient accumulated, in fast memory.

``kernels.sparse_blocks`` (PR 39) with a payload axis: a block column
holds ``payload = 1 + k`` floats (a column's weight and its factors), the
parameter table lies ``[payload, dim / 128, 128]``, a float of the payload
a plane, and a blocked slot's cells read, or add to, ``length / 128``
consecutive rows of every plane. ``ops.sparse.block_lookup`` /
``block_accumulate`` (their payload forms) are these kernels' reference
and what every backend but a TPU runs. Through XLA each slot's one-hots,
its product's rows ``[batch, c, payload]`` of which one column in ``c``
is kept, the turned blocks, the masks and the cells' gradients go through
HBM; here a tile of the batch (up to :data:`TILE` rows, along the LANES)
meets a slot's block in fast memory and only what the algorithm needs is
written: the looked-up rows ``xp [slots, payload, batch]`` (the gradient
reads them) and the blocks' sums.

*Two bodies a direction, whatever the plan.* A LONG block (more than
:data:`SHORT` columns) is walked in CHUNKS of :data:`CHUNK_ROWS` = 16 of
its rows of 128 columns (``local = 128 * hi + lo``). A chunk's product
contracts ``lo`` (128 lanes: a whole MXU tile, nothing padded) while the
rows of every float of the payload stream through: the lookup's as four
int8 digits of the float's 32 bits, ``[4 * payload * 16, 128] @ [128,
tile]`` in int32 (1,088 rows at a payload of 17, at int8's rate, twice
bfloat16's), the accumulation's as three bfloat16 parts of the gradient,
``[3 * payload * 16, tile] x [128, tile]`` in float32 (816 rows). So the
MXU's weights (the one-hot) are loaded once for a thousand rows whatever
the block's length, and ``hi`` picks the row among the chunk's 16. The
grid is ``(slots, tiles)``, the slot outermost: a slot's digits (or its
sums) are fetched when the slot changes and stay while the tiles pass;
how many chunks a slot has is a table in SMEM, so the loop runs a slot's
own chunks and the padding up to the plan's longest block is never
multiplied. A SHORT block (128 or 256 columns: 21 of ``fm-criteo``'s 39
slots, 3,968 of its 269,696 columns) would pay a whole chunk for an
eighth of one: its one-hot over ALL its columns is contracted instead
(three bfloat16 parts, ``[3 * 32, 256] @ [256, tile]``), nothing to
pick, every short slot of a tile in one grid step; so the short kernels'
tile shrinks as the short slots grow in number (:func:`short_tile_rows`:
``fm-criteo``'s 21 keep the long kernels' 4,096 rows, 37 take 2,048, and
a table of 130 narrow fields keeps XLA's walk).

*How a chunk's rows lie* (:func:`planes`). Picking one of 16 rows with
the payload's floats one after another leaves a sum down the sublanes a
float of the payload and grid step. So the last ``wide`` floats of the
payload (whole bfloat16 tiles of them: a factorization machine's 16
factors) lie ALONG the sublanes under each of the chunk's rows: the row
picks whole vregs and nothing is summed down; the first ``low`` floats
(the weight) keep the chunk's rows along the sublanes. The same rows the
other way round in the accumulation.

*The lookup stays the gather bit for bit.* A 0/1 operand is exact and a
column is named once, so a product is the table's own value: a long
block's float comes back as the four digits of its bits
(``_split.digits``: a selection needs no arithmetic on the float, so its
bits can travel as integers), a short
block's as its three bfloat16 parts, added as they lie, ``(hi + mid) +
lo``, for ``sparse_blocks``' reasons. *The accumulation is exact in
float32*: the cell's gradient ``(mult x_s)(base - [0, xp_f])`` is made in
the kernel, as ``_fm_sparse.make_step`` states it, split in three
bfloat16 parts (sums of floats cannot travel as digits), put on the
cell's row among the chunk's 16, contracted over the tile with the 0/1
mask of ``lo`` and summed in float32 into an output block that stays in
VMEM while a slot's tiles pass: one fixed order, no atomics, the same
bits every run. Putting a part on its row is ONE ``and`` a 32-bit word
(two bfloat16 rows of a packed tile; the parts' bits against a mask of
the row's half or whole word): a select in float32 and a pack, three
times the vector unit's work, held the long accumulation at 71 % of the
MXU's own time, and a loop over the payload between the words and the
product at 63 % (the words have to be made in the product's own
expression to run beside it; PERF.md section 6, PR 53). The accumulation
is a kernel because the lookup is: XLA's long accumulations run near the
MXU's own time on ``xp`` as XLA's own lookup lays it, and fed from these
kernels' ``xp [slots, payload, batch]`` they read 17.04 ms a step of
``fm-criteo.fit`` where these read 10.86, the step 25.27 for 19.30 (a
chip, PR 53's review; ``tools/fm_walk_probe.py`` reads it again).

Traced in 32-bit mode whatever the caller's (PR 30). ``sparse_blocks``'
vocabulary that is no traced body is shared (``LANES``, the one-hot and
mask helpers, ``_split.rounded_parts``, the SMEM tables ``where`` and
``starts``); its bodies are not touched.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

from flinkml_tpu.kernels._split import digits, joined_digits, rounded_parts
from flinkml_tpu.kernels.sparse_blocks import (
    LANES, PACKED, SUBLANES, TILE, _as_operand, _one_hot)

#: Rows of 128 columns a chunk of a block holds: a bfloat16 tile.
CHUNK_ROWS = PACKED
#: The most columns of a SHORT block: one product over all of them, no
#: row to pick.
SHORT = 256
#: Fast memory the kernels may use (a v5e has 128 MiB, the compiler's own
#: limit is 16), what of it a grid step's values may take, what a slot's
#: parts, or its sums, in their two buffers, and what a step of the SHORT
#: kernels, which meets every short slot at once.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_STEP_BYTES = 28 * 1024 * 1024
_RESIDENT_BYTES = 24 * 1024 * 1024
_SHORT_BYTES = 48 * 1024 * 1024
#: Bytes a grid step holds for each float of the payload and batch row:
#: a chunk's product over 16 rows (four digits in int32, or three parts
#: in float32 and their sum), the picked rows, the partial sums.
_LIVE_BYTES = CHUNK_ROWS * 4 * 5 + SUBLANES * 4


def chunks_of(length: int) -> int:
    """The chunks of :data:`CHUNK_ROWS` rows a block of ``length``
    columns is walked in."""
    return -(-length // (LANES * CHUNK_ROWS))


def planes(payload: int) -> Tuple[int, int]:
    """``(low, wide)``: how a chunk's rows are laid (module docstring).
    The last ``wide`` floats of the payload, whole bfloat16 tiles of
    them, lie along the sublanes under each of the chunk's 16 rows; the
    first ``low`` have the chunk's rows along the sublanes, a float of
    the payload after another."""
    wide = payload // PACKED * PACKED
    return payload - wide, wide


def tile_rows(batch: int, payload: int) -> Optional[int]:
    """Batch rows a grid step: the most, of ``sparse_blocks.TILE`` halved
    down to 128, that divide the batch and keep what a step makes of a
    chunk inside :data:`_STEP_BYTES`; None where none does."""
    tile = TILE
    while tile >= LANES:
        if batch % tile == 0 and _LIVE_BYTES * payload * tile <= _STEP_BYTES:
            return tile
        tile //= 2
    return None


def short_tile_rows(batch: int, payload: int, slots: int, columns: int,
                    width: int) -> Optional[int]:
    """Batch rows a grid step of the SHORT kernels, which hold a tile of
    EVERY short slot at once: the most, of :func:`tile_rows`' halved down
    to 128, that keep inside :data:`_SHORT_BYTES` the ``slots`` slots'
    looked-up rows, their sums ``[columns, 3 payload]`` (128 lanes at the
    least) and their parts, the step's cells and values (``width`` rows
    of each), all in two buffers, and a product's one-hot and parts; None
    where none does (a table of a hundred narrow fields: Mosaic would
    refuse the kernel at the fit, PR 53's review)."""
    tile = tile_rows(batch, payload)
    rows = -(-payload // SUBLANES) * SUBLANES
    padded = _padded(payload)
    while tile is not None and tile >= LANES:
        if (2 * 4 * tile * (slots * rows + 2 * width + rows + SUBLANES)
                + slots * columns * (2 * 4 * max(LANES, 3 * padded)
                                     + 2 * 2 * 3 * padded)
                + tile * (2 * columns + 2 * 4 * 3 * padded)) <= _SHORT_BYTES:
            return tile
        tile //= 2
    return None


def unsupported_reason(dtype, batch: int, lengths: Sequence[int],
                       payload: int, width: Optional[int] = None) -> Optional[str]:
    """Why the kernels do not take this step (None = they do):
    ``lengths`` the plan's blocked slots' block lengths, ``payload`` the
    floats a column, ``width`` the cells a row of the step has (the
    blocked slots alone if not given)."""
    import jax.numpy as jnp

    if jnp.dtype(dtype) != jnp.float32:
        return f"parameters {dtype}: the parts are a float32's"
    if not lengths:
        return "no blocked slot"
    if any(length % LANES for length in lengths):
        return f"a block is whole rows of {LANES}"
    longest = max(lengths)
    # Four int8 digits of a column's payload, or its float32 sums, in two
    # buffers each.
    if 2 * 4 * payload * chunks_of(longest) * CHUNK_ROWS * LANES > _RESIDENT_BYTES:
        return (f"a block of {longest} columns x {payload} floats: its "
                "digits, or its sums, would not stay in fast memory")
    if tile_rows(batch, payload) is None:
        return f"a batch of {batch} rows a device is not whole tiles of {LANES}"
    short = [length for length in lengths if length <= SHORT]
    if short and short_tile_rows(batch, payload, len(short), max(short),
                                 width or len(lengths)) is None:
        return (f"{len(short)} blocks of up to {SHORT} columns x {payload} "
                "floats: a tile of them all would not stay in fast memory")
    # The rows' sums over the slots (and eight sublanes of squares) stay
    # for the lookup's whole grid, in two buffers.
    if 2 * 4 * (-(-payload // SUBLANES) + 1) * SUBLANES * batch > _RESIDENT_BYTES:
        return (f"a batch of {batch} rows a device x {payload} floats: "
                "the rows' sums would not stay in fast memory")
    return None


def _chunked(blocks, payload: int):
    """``[slots, payload, chunks * 16, 128]`` as the chunks' rows lie in
    the kernels, ``[slots, chunks, 16 * payload, 128]``: under each of a
    chunk's 16 rows the wide floats of the payload, then each low float's
    16 rows (:func:`planes`). Whole rows of 128 columns move; nothing is
    turned."""
    import jax.numpy as jnp

    low, wide = planes(payload)
    slots, _, rows, _ = blocks.shape
    chunks = rows // CHUNK_ROWS
    by_chunk = blocks.reshape(slots, payload, chunks, CHUNK_ROWS, LANES)
    return jnp.concatenate([
        by_chunk[:, low:].transpose(0, 2, 3, 1, 4).reshape(
            slots, chunks, CHUNK_ROWS * wide, LANES),
        by_chunk[:, :low].transpose(0, 2, 1, 3, 4).reshape(
            slots, chunks, low * CHUNK_ROWS, LANES)], axis=2)


def _unchunked(sums, payload: int):
    """:func:`_chunked`'s inverse."""
    import jax.numpy as jnp

    low, wide = planes(payload)
    slots, chunks = sums.shape[:2]
    return jnp.concatenate([
        sums[:, :, CHUNK_ROWS * wide:].reshape(
            slots, chunks, low, CHUNK_ROWS, LANES).transpose(0, 2, 1, 3, 4),
        sums[:, :, :CHUNK_ROWS * wide].reshape(
            slots, chunks, CHUNK_ROWS, wide, LANES).transpose(0, 3, 1, 2, 4)],
        axis=1).reshape(slots, payload, chunks * CHUNK_ROWS, LANES)


def block_digits(table, lengths: Sequence[int], first):
    """The long slots' blocks as the lookup's left operand, ``[slots,
    chunks, 4, 16 * payload, 128]`` int8: slot ``i``'s ``lengths[i] /
    128`` rows of every plane of ``table [payload, dim / 128, 128]`` from
    row ``first[i]`` on, zeros after them up to the longest block's whole
    chunks, a chunk's rows as :func:`_chunked` lays them, each float's 32
    bits in four digits (``_split.digits``). Made once a step by XLA."""
    import jax
    import jax.numpy as jnp

    rows = CHUNK_ROWS * max(chunks_of(length) for length in lengths)
    blocks = jnp.stack([
        jnp.pad(jax.lax.dynamic_slice_in_dim(table, at, length // LANES, axis=1),
                ((0, 0), (0, rows - length // LANES), (0, 0)))
        for length, at in zip(lengths, first)])
    return jnp.stack([_chunked(digit, table.shape[0])
                      for digit in digits(blocks)], axis=2)


def _cells_of(where_ref, starts_ref, cells_ref, vals_ref):
    """This grid step's slot: its cells' row of 128 columns and lane in
    its block, and their values, ``[1, tile]`` each. ``where_ref`` (SMEM)
    says which row of the step's cells the slot is (the block handed is
    the eight rows it lies in), ``starts_ref`` at which row of 128 columns
    its block starts."""
    from jax.experimental import pallas as pl

    slot = where_ref[pl.program_id(0)]
    row = pl.ds(slot % SUBLANES, 1)
    local = cells_ref[row, :] - LANES * starts_ref[slot]
    return local >> 7, local & (LANES - 1), vals_ref[row, :]


def _picked(rows, half, payload: int):
    """The cell's row of a chunk's ``rows [16 * payload, tile]`` (as
    :func:`_chunked` lays them): ``[wide / 8, 8, tile]`` of the wide
    floats, whole vregs the row picks (no sum down the sublanes is left
    for the end), and ``[low, 8, tile]`` of the low ones, a float's 16
    rows folded to eight sublanes of which one is the cell's. ``half [1,
    tile]`` is the cell's row among the chunk's 16; another row is none's."""
    import jax.numpy as jnp

    low, wide = planes(payload)
    tile = rows.shape[1]
    out = []
    if wide:
        under = rows[:CHUNK_ROWS * wide].reshape(
            CHUNK_ROWS, wide // SUBLANES, SUBLANES, tile)
        row_of = jnp.broadcast_to(half, (SUBLANES, tile))
        picked = jnp.zeros(under.shape[1:], rows.dtype)
        for h in range(CHUNK_ROWS):
            picked = jnp.where(row_of == h, under[h], picked)
        out.append(picked)
    if low:
        picked = jnp.where(
            _one_hot(half, CHUNK_ROWS)[None],
            rows[CHUNK_ROWS * wide:].reshape(low, CHUNK_ROWS, tile), 0)
        out.append(picked[:, :SUBLANES] + picked[:, SUBLANES:])
    return out


def _lookup_body(where_ref, starts_ref, chunks_ref, cells_ref, vals_ref,
                 digits_ref, out_ref, sums_ref, squares_ref, *acc_refs):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    _, payload, tile = out_ref.shape
    low, wide = planes(payload)
    hi, lo, vals = _cells_of(where_ref, starts_ref, cells_ref, vals_ref)
    lanes_of = jnp.where(_one_hot(lo, LANES), 1, 0).astype(jnp.int8)
    for acc_ref in acc_refs:
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.int32)
    each = payload * CHUNK_ROWS

    def one_chunk(j, carry):
        # The lane is contracted; the rows of every digit and float of
        # the payload stream through it; the row picks each digit's.
        four = jnp.dot(digits_ref[0, j].reshape(4 * each, LANES), lanes_of,
                       preferred_element_type=jnp.int32)
        for k in range(4):
            for acc_ref, picked in zip(acc_refs, _picked(
                    four[k * each:(k + 1) * each], hi - j * CHUNK_ROWS, payload)):
                acc_ref[k] += picked
        return carry

    jax.lax.fori_loop(0, chunks_ref[pl.program_id(0)], one_chunk, 0)

    def floats(acc_ref):
        """The picked digits put together: the looked-up floats' bits."""
        return jax.lax.bitcast_convert_type(
            joined_digits(acc_ref), jnp.float32)

    # This tile's lanes of the rows' sums over the slots, which stay for
    # the whole grid: sum_s xp, and sum_s sum_f xp_f ** 2 over the factors
    # (every float of the payload but the first) down to eight sublanes.
    here = pl.ds(pl.multiple_of(pl.program_id(1) * tile, tile), tile)

    @pl.when(pl.program_id(0) == 0)
    def _():
        sums_ref[:, here] = jnp.zeros((payload, tile), jnp.float32)
        squares_ref[:, here] = jnp.zeros((SUBLANES, tile), jnp.float32)

    accs = iter(acc_refs)
    if wide:
        under = vals * floats(next(accs)).reshape(wide, tile)
        out_ref[0, low:, :] = under
        sums_ref[low:, here] += under
        squares_ref[:, here] += jnp.sum(jnp.square(under).reshape(
            wide // SUBLANES, SUBLANES, tile), axis=0)
    if low:
        picked = floats(next(accs))
        for p in range(low):
            row = vals * jnp.sum(picked[p], axis=0, keepdims=True)
            out_ref[0, p:p + 1, :] = row
            sums_ref[p:p + 1, here] += row
            if p:
                squares_ref[:1, here] += jnp.square(row)


def _cell_grads(mult, vals, base, xp):
    """``(mult x_s) (base - [0, x_s V[i_s, f]])``: the cell's gradient,
    as ``_fm_sparse.make_step`` states it, ``[payload, tile]``."""
    import jax
    import jax.numpy as jnp

    factors = jax.lax.broadcasted_iota(jnp.int32, base.shape, 0) > 0
    return (mult * vals) * (base - jnp.where(factors, xp, 0.0))


def _accumulate_body(where_ref, starts_ref, chunks_ref, cells_ref, vals_ref,
                     mult_ref, base_ref, xp_ref, out_ref, *word_refs):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)

    payload, tile = base_ref.shape
    low, wide = planes(payload)
    hi, lo, vals = _cells_of(where_ref, starts_ref, cells_ref, vals_ref)
    lanes_of = _as_operand(_one_hot(lo, LANES))
    grads = _cell_grads(mult_ref[...], vals, base_ref[...], xp_ref[0])
    # The gradient's three parts as the 16 bits of their bfloat16s in
    # 32-bit words (rows 2 i and 2 i + 1 of a packed tile are word i's
    # low and high half). The wide floats as they pack; a low float twice
    # in a word of its own row, to be broadcast down a chunk's rows.
    refs = iter(word_refs)
    if wide:
        wide_ref = next(refs)
        for k, part in enumerate(rounded_parts(grads[low:], in_kernel=True)):
            wide_ref[k] = pltpu.bitcast(part, jnp.uint32).reshape(
                wide // PACKED, SUBLANES, tile)
    if low:
        low_ref = next(refs)
        for k, part in enumerate(rounded_parts(grads[:low], in_kernel=True)):
            bits = pltpu.bitcast(part.astype(jnp.float32), jnp.uint32)
            bits = bits | (bits >> 16)
            for p in range(low):
                low_ref[k * low + p] = bits[p:p + 1]
    each = payload * CHUNK_ROWS
    upper = jnp.where((hi & 1) == 1, jnp.uint32(0xFFFF0000), jnp.uint32(0xFFFF))
    every = jnp.uint32(0xFFFFFFFF)

    def one_chunk(j, carry):
        # A part on its cell's row among the chunk's 16 is one AND a word
        # (no select in float32 and no packing), one expression with the
        # product, so that the words are made while the MXU runs.
        half = hi - j * CHUNK_ROWS
        spread = []
        if wide:
            # [16, 8, tile]: all of a word where the row is the cell's.
            row_of = jnp.broadcast_to(half, (SUBLANES, tile))[None]
            whole = jnp.where(
                row_of == jax.lax.broadcasted_iota(
                    jnp.int32, (CHUNK_ROWS, SUBLANES, tile), 0),
                every, jnp.uint32(0))
            spread.append((whole[None, :, None] & wide_ref[...][:, None]).reshape(
                3, CHUNK_ROWS * wide // 2, tile))
        if low:
            # [8, tile]: the half of word i that is row 2 i or 2 i + 1.
            word_of = jnp.where(_one_hot(half >> 1, SUBLANES),
                                jnp.broadcast_to(upper, (SUBLANES, tile)),
                                jnp.uint32(0))
            spread.append((word_of[None] & low_ref[...]).reshape(
                3, low * SUBLANES, tile))
        spread = jnp.concatenate(spread, axis=1).reshape(3 * each // 2, tile)
        # The columns' mask is 0/1; the tile is contracted.
        three = jax.lax.dot_general(
            pltpu.bitcast(spread, jnp.bfloat16), lanes_of,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        out_ref[0, j] += (three[:each] + three[each:2 * each]) + three[2 * each:]
        return carry

    jax.lax.fori_loop(0, chunks_ref[pl.program_id(0)], one_chunk, 0)


def _short_lookup_body(where_ref, starts_ref, cells_ref, vals_ref, parts_ref,
                       out_ref, sums_ref, squares_ref):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    slots, payload, tile = out_ref.shape
    padded, columns = parts_ref.shape[1] // 3, parts_ref.shape[2]
    factors = jax.lax.broadcasted_iota(jnp.int32, (padded, tile), 0) > 0
    sums_ref[...] = jnp.zeros(sums_ref.shape, jnp.float32)
    squares_ref[...] = jnp.zeros(squares_ref.shape, jnp.float32)

    def one_slot(i, carry):
        # The whole block's one-hot is contracted: the three parts of
        # every float of the payload, one under the other, come out as
        # the looked-up row's, and there is nothing to pick.
        slot = where_ref[i]
        local = cells_ref[pl.ds(slot, 1), :] - LANES * starts_ref[slot]
        three = jnp.dot(parts_ref[i], _as_operand(_one_hot(local, columns)),
                        preferred_element_type=jnp.float32)
        rows = vals_ref[pl.ds(slot, 1), :] * (
            (three[:padded] + three[padded:2 * padded]) + three[2 * padded:])
        out_ref[i] = rows[:payload]
        sums_ref[...] += rows[:payload]
        squares_ref[...] += jnp.sum(jnp.where(factors, jnp.square(rows), 0.0).reshape(
            padded // SUBLANES, SUBLANES, tile), axis=0)
        return carry

    jax.lax.fori_loop(0, slots, one_slot, 0)


def _short_accumulate_body(where_ref, starts_ref, cells_ref, vals_ref, mult_ref,
                           base_ref, xp_ref, out_ref, parts_ref):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)
        parts_ref[...] = jnp.zeros(parts_ref.shape, jnp.float32)

    slots, columns, _ = out_ref.shape
    payload, tile = base_ref.shape
    padded = parts_ref.shape[0] // 3
    mult, base = mult_ref[...], base_ref[...]

    def one_slot(i, carry):
        slot = where_ref[i]
        local = cells_ref[pl.ds(slot, 1), :] - LANES * starts_ref[slot]
        grads = _cell_grads(mult, vals_ref[pl.ds(slot, 1), :], base, xp_ref[i])
        # The three parts one under the other (rows of zeros between),
        # the whole block's one-hot streams through them, the tile is
        # contracted: [columns, tile] x [3 padded, tile].
        for k, part in enumerate(rounded_parts(grads, in_kernel=True)):
            parts_ref[k * padded:k * padded + payload, :] = part.astype(
                jnp.float32)
        out_ref[i] += jax.lax.dot_general(
            _as_operand(_one_hot(local, columns)),
            parts_ref[...].astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, slots, one_slot, 0)


def _call(body, grid, prefetch, operands, in_specs, out_specs, out_shape,
          scratch, interpret, name: str):
    """One kernel: ``prefetch`` (``where``, the walked slots' rows of the
    cells, ``starts``, and for the long slots their chunks) goes to SMEM
    before the grid runs."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from flinkml_tpu.kernels import _mosaic

    if interpret is None:
        interpret = _mosaic.interpret_mode()
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=grid,
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret, name=name,
    )(*(jnp.asarray(table, jnp.int32) for table in prefetch), *operands)


def _eight_rows(tile: int):
    """``[width, batch]``: the eight rows this grid step's slot lies in,
    a tile of the batch at a time."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec((SUBLANES, tile),
                        lambda s, t, where, *_: (where[s] // SUBLANES, t))


def _tiles(shape, tile: int, slots: bool = True):
    """``[*shape, batch]`` a tile of the batch at a time, the grid
    ``(slots, tiles)`` or the tiles alone."""
    from jax.experimental import pallas as pl

    zeros = (0,) * len(shape)
    if slots:
        return pl.BlockSpec(tuple(shape) + (tile,), lambda s, t, *_: zeros + (t,))
    return pl.BlockSpec(tuple(shape) + (tile,), lambda t, *_: zeros + (t,))


def _whole(shape, slots: bool = False):
    """An operand that stays for the whole grid, of tiles or of ``(slots,
    tiles)``."""
    from jax.experimental import pallas as pl

    if slots:
        return pl.BlockSpec(tuple(shape), lambda s, t, *_: (0,) * len(shape))
    return pl.BlockSpec(tuple(shape), lambda t, *_: (0,) * len(shape))


def _of_slot(shape):
    """A slot's whole part of ``[slots, ...]``."""
    from jax.experimental import pallas as pl

    zeros = (0,) * len(shape)
    return pl.BlockSpec((1,) + tuple(shape), lambda s, t, *_: (s,) + zeros)


def _slot_tiles(payload: int, tile: int):
    """``xp [slots, payload, batch]``: a slot's rows, a tile at a time."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec((1, payload, tile), lambda s, t, *_: (s, 0, t))


def _kinds(lengths: Sequence[int]):
    """Which of the walked slots are short (one product over the whole
    block) and which long (chunks): ``(short, long)``, their places in
    the walk."""
    return ([i for i, length in enumerate(lengths) if length <= SHORT],
            [i for i, length in enumerate(lengths) if length > SHORT])


def _padded(payload: int) -> int:
    """``payload`` in whole bfloat16 tiles."""
    return -(-payload // PACKED) * PACKED


def _long_scratch(payload: int, tile: int, lookup: bool):
    """The long kernels' scratch: the lookup's partial sums, or the
    accumulation's words, of the wide and of the low floats
    (:func:`planes`)."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    low, wide = planes(payload)
    if lookup:
        shapes = [(4, wide // SUBLANES, SUBLANES, tile)] * bool(wide) + [
            (4, low, SUBLANES, tile)] * bool(low)
        return [pltpu.VMEM(shape, jnp.int32) for shape in shapes]
    shapes = [(3, wide // PACKED, SUBLANES, tile)] * bool(wide) + [
        (3 * low, 1, tile)] * bool(low)
    return [pltpu.VMEM(shape, jnp.uint32) for shape in shapes]


def lookup(lengths: Sequence[int], where: Sequence[int], table, cells, vals,
           starts, *, interpret: Optional[bool] = None):
    """``xp[s, :, b] = vals[s, b] * table[:, cells[s, b]]`` over the
    walked slots ``s``, float32: what a cell adds to its row's sums.
    ``(xps, sums, squares)``: ``xps`` one ``[slots, payload, batch]``
    array for the short slots (if any) and one for the long ones (if
    any), each kind's in their turn (:func:`accumulate` takes them back);
    ``sums [payload, batch]`` their sum over the slots and ``squares
    [batch]`` the sum over the slots and over every float of the payload
    but the first (a factorization machine's factors) of their squares,
    both made in fast memory beside the lookup. ``lengths`` the walked
    slots' block lengths and ``where`` the rows of ``cells [width,
    batch]`` int32 (a step's cells, a slot a row: their columns) and
    ``vals [width, batch]`` float32 that are those slots, in turn (rows
    not named are not read); ``table [payload, dim / 128, 128]`` float32, column
    ``128 r + l`` at ``[:, r, l]``; ``starts [width]`` int32 the row of
    128 columns each slot's block starts at. Each looked-up float is the
    table's bit for bit; a cell outside its block reads 0 or some float
    of the table and must carry the value 0. ``batch`` is whole tiles
    (:func:`tile_rows`)."""
    import jax
    import jax.numpy as jnp

    from flinkml_tpu.kernels import _mosaic

    payload = table.shape[0]
    width, batch = cells.shape
    short, long = _kinds(lengths)
    xps, sums, squares = [], 0.0, 0.0

    def out_shapes(slots, *operands):
        return [_mosaic.out_struct(shape, jnp.float32, cells, vals, starts, *operands)
                for shape in ((slots, payload, batch), (payload, batch),
                              (SUBLANES, batch))]

    with jax.enable_x64(False):
        if short:
            columns, padded = max(lengths[i] for i in short), _padded(payload)
            tile = short_tile_rows(batch, payload, len(short), columns, width)
            # [slots, 3 padded, columns]: a part's floats of the payload
            # one under the other, a block's columns along the lanes.
            parts = jnp.concatenate(rounded_parts(jnp.stack([
                jnp.pad(jax.lax.dynamic_slice_in_dim(
                    table, starts[where[i]], lengths[i] // LANES, axis=1
                ).reshape(payload, lengths[i]),
                    ((0, padded - payload), (0, columns - lengths[i])))
                for i in short]), in_kernel=False), axis=1)
            xp, some, squared = _call(
                _short_lookup_body, (batch // tile,),
                ([where[i] for i in short], starts), [cells, vals, parts],
                [_tiles((width,), tile, False)] * 2 + [_whole(parts.shape)],
                [_tiles((len(short), payload), tile, False),
                 _tiles((payload,), tile, False), _tiles((SUBLANES,), tile, False)],
                out_shapes(len(short), parts),
                [], interpret, "flinkml.fm.lookup.short")
            xps, sums, squares = xps + [xp], sums + some, squares + squared
        if long:
            tile = tile_rows(batch, payload)
            parts = block_digits(table, [lengths[i] for i in long],
                                 [starts[where[i]] for i in long])
            xp, some, squared = _call(
                _lookup_body, (len(long), batch // tile),
                ([where[i] for i in long], starts,
                 [chunks_of(lengths[i]) for i in long]), [cells, vals, parts],
                [_eight_rows(tile)] * 2 + [_of_slot(parts.shape[1:])],
                [_slot_tiles(payload, tile), _whole((payload, batch), True),
                 _whole((SUBLANES, batch), True)],
                out_shapes(len(long), parts),
                _long_scratch(payload, tile, True),
                interpret, "flinkml.fm.lookup.long")
            xps, sums, squares = xps + [xp], sums + some, squares + squared
        return xps, sums, jnp.sum(squares, axis=0)


def accumulate(lengths: Sequence[int], where: Sequence[int], cells, vals,
               starts, mult, base, xps, *, interpret: Optional[bool] = None):
    """Each walked slot's ``zeros([payload, length]).at[:, cells[s, b] -
    128 * starts[s]].add(g[s, :, b])`` as ``[payload, length / 128, 128]``
    float32, a list in the slots' turn, where ``g[s, :, b] = (mult[b] *
    vals[s, b]) * (base[:, b] - [0, xp[s, 1:, b]])`` is the cell's
    gradient: ``mult [batch]``, ``base [payload, batch]`` (a row's ``[1,
    S_f]``) and ``xps`` what :func:`lookup` gave, the other operands its
    own. The products exact, the sums float32 in one fixed order (a
    tile's cells on the MXU, the tiles in turn): the same bits every run.
    A cell outside its block must carry the value 0."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    from flinkml_tpu.kernels import _mosaic

    payload, batch = base.shape
    width = cells.shape[0]
    short, long = _kinds(lengths)
    xps = iter(xps)
    out = [None] * len(lengths)
    with jax.enable_x64(False):
        row = mult[None, :]
        if short:
            xp = next(xps)
            columns, padded = max(lengths[i] for i in short), _padded(payload)
            tile = short_tile_rows(batch, payload, len(short), columns, width)
            sums = _call(
                _short_accumulate_body, (batch // tile,),
                ([where[i] for i in short], starts),
                [cells, vals, row, base, xp],
                [_tiles((width,), tile, False)] * 2
                + [_tiles((1,), tile, False), _tiles((payload,), tile, False),
                   _tiles((len(short), payload), tile, False)],
                _whole((len(short), columns, 3 * padded)),
                _mosaic.out_struct((len(short), columns, 3 * padded), jnp.float32,
                                 cells, vals, starts, mult, base, xp),
                [pltpu.VMEM((3 * padded, tile), jnp.float32)],
                interpret, "flinkml.fm.accumulate.short")
            # The parts added as they lie and the block's columns turned
            # to lanes: a few hundred kilobytes, XLA's.
            sums = sums.reshape(len(short), columns, 3, padded)
            sums = ((sums[:, :, 0] + sums[:, :, 1]) + sums[:, :, 2])[..., :payload]
            for at, i in enumerate(short):
                out[i] = sums[at, :lengths[i]].T.reshape(payload, -1, LANES)
        if long:
            xp = next(xps)
            tile = tile_rows(batch, payload)
            chunks = max(chunks_of(lengths[i]) for i in long)
            shape = (chunks, CHUNK_ROWS * payload, LANES)
            sums = _unchunked(_call(
                _accumulate_body, (len(long), batch // tile),
                ([where[i] for i in long], starts,
                 [chunks_of(lengths[i]) for i in long]),
                [cells, vals, row, base, xp],
                [_eight_rows(tile)] * 2 + [_tiles((1,), tile),
                                           _tiles((payload,), tile),
                                           _slot_tiles(payload, tile)],
                _of_slot(shape),
                _mosaic.out_struct((len(long),) + shape, jnp.float32,
                                 cells, vals, starts, mult, base, xp),
                _long_scratch(payload, tile, False),
                interpret, "flinkml.fm.accumulate.long"), payload)
            for at, i in enumerate(long):
                out[i] = sums[at, :, :lengths[i] // LANES]
    return out
