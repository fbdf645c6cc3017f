"""Pallas row fetch — ``table.at[ids].get()`` where most ids name a few
HOT rows: the hot rows are read out of fast memory a slot at a time, and
XLA's gather runs over the COLD slots alone, compacted.

XLA's gather of float32 rows of 128 lanes costs 9.5 ns a row on a v5e
whatever the row's neighbours (54 GB/s, 6.6 % of HBM's rate: PERF.md
section 5), and a DMA cannot beat it a row at a time (a slice of HBM is
whole groups of eight, and under 12 KB a DMA costs its issue by the
scalar core: PR 45). But a table whose ids are skewed, as a rating
table's items and users are, names the same few rows most of the time,
and a v5e's fast memory holds 65,536 rows of 512 bytes with room.

*The host's half* (:func:`ranks` and :func:`localize`, NumPy, once a
table). The ids of one call (a chunk of ``n`` slots) are cut in TILES of :data:`TILE` slots.
A slot's id becomes a LOCAL index: the row's rank among the hot rows if
it is one, else ``hot + (tile % 2) * TILE + k`` where ``k`` is the slot's
place among its tile's cold slots. Beside it the call's cold ids in slot
order, each tile's run padded to whole groups of eight (``starts`` says
where a tile's run begins) and the call's to whole blocks of
:data:`BLOCK`, which the caller's program fetches with XLA's gather, a
block at a time, before the kernel runs (:func:`fetch_cold`).

*The kernel* (:func:`fetch`). ONE buffer in fast memory, ``rows [hot + 2
TILE, lanes]``: the hot rows copied into ``[0, hot)`` at the call's first
grid step and left there; behind them two tiles' worth of cold rows. A
grid step is a tile: its run of the fetched cold rows (``cap`` rows, the
longest run of the table rounded up to a power of two, one DMA) was started a step ahead into the
half that ``tile % 2`` names, the next tile's is started into the other,
and the body is ``out[j] = rows[loc[j]]``, thirty-two slots unrolled, ``loc``
a tile in SMEM, ``out`` streamed back by its ``BlockSpec``. One path: a
hot and a cold slot are the same read. The rows are COPIED: the result
equals the gather's to the bit.

*Where it pays* (:func:`unsupported_reason`). A slot costs the kernel
``t_k`` (:data:`KERNEL_NS_A_SLOT`) whether hot or cold, and a cold slot
XLA's :data:`GATHER_NS_A_ROW` besides; the gather alone costs every slot
that. So the kernel wins where ``cold * 9.5 + t_k < 9.5``, that is ``(1 -
cold) * 9.5 > t_k``: where the hot rows cover more than ``t_k / 9.5`` of
the slots, and :data:`MIN_HOT_SHARE` asks a tenth of the slots more. Both
numbers are readings on a v5e (``tools/als_fetch_probe.py``;
docs/development/kernels.md has them).

Traced in 32-bit mode whatever the caller's (PR 30: a 64-bit block
aborts the process in Mosaic).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import numpy as np

#: Lanes of a vreg: a row is whole rows of them.
LANES = 128
#: Rows a DMA moves: the sublanes of a float32 tile. A tile's run of cold
#: rows starts and ends on one.
GROUP = 8
#: Slots a grid step: a multiple of 1,024, as the chip tiles a vector of
#: int32 in SMEM. The same for every call, so that a program's many call
#: sites hold one kernel body.
TILE = 2048
#: Slots a turn of the kernel's loop at most: 2.45 ns a slot where sixteen
#: read 2.59 and eight 2.89 (``tools/als_fetch_probe.py``).
UNROLL = 32
#: Cold ids a gather: a call's cold rows are fetched in whole blocks, as
#: many as it has, so that no shape of the program depends on the table's
#: cold ids (a program compiled for one table is another table's too).
BLOCK = 4096
#: Most hot rows: 32 MiB of a v5e's 128 MiB of fast memory.
HOT_ROWS = 1 << 16
#: What the kernel may take of fast memory: the hot rows, two tiles of
#: cold rows, two blocks of the output, and room.
VMEM_LIMIT_BYTES = 48 << 20
#: ns a row of XLA's gather of 512-byte rows (PERF.md section 5: 9.0-9.5
#: in ``als_half_step``), and ns a slot of the kernel with the hot rows'
#: copy a call (2.45-2.89 by unroll: ``tools/als_fetch_probe.py``), on a
#: v5e.
GATHER_NS_A_ROW = 9.5
KERNEL_NS_A_SLOT = 3.0
#: The share of a table's slots its hot rows must cover: above ``t_k /
#: 9.5`` by a tenth of the whole, for the cold runs' padding and the run
#: more a call fetches.
MIN_HOT_SHARE = KERNEL_NS_A_SLOT / GATHER_NS_A_ROW + 0.1


def hot_rows(rows: int) -> int:
    """Hot rows a table of ``rows`` rows gets: the power of two that
    holds them all, at most :data:`HOT_ROWS`."""
    return min(HOT_ROWS, max(GROUP, 1 << (rows - 1).bit_length()))


def unsupported_reason(dtype, lanes: int, hot_share: float) -> Optional[str]:
    """Why the kernel does not fetch this table's rows (None = it does):
    read off the backend, the table's dtype and how much of the slots the
    hot rows cover, nothing else."""
    import jax.numpy as jnp

    from flinkml_tpu.kernels import _mosaic

    if _mosaic.interpret_mode():
        return "not a TPU: Mosaic's kernel would run interpreted"
    if jnp.dtype(dtype) != jnp.float32:
        return f"a {jnp.dtype(dtype).name} table: a row is float32's sublane"
    if lanes % LANES:
        return f"rows of {lanes} floats: a row is whole rows of {LANES} lanes"
    if hot_share < MIN_HOT_SHARE:
        return (f"the hot rows cover {hot_share:.3f} of the slots, under "
                f"{MIN_HOT_SHARE:.3f}: the kernel's {KERNEL_NS_A_SLOT} ns a slot "
                f"would not pay for the gather's {GATHER_NS_A_ROW} ns a row saved")
    return None


class Local(NamedTuple):
    """:func:`localize`'s: what a batch of calls' slots hold beside their
    local indices."""

    cold: np.ndarray      # [sum of lengths] int32: the calls' cold ids, back to back
    lengths: np.ndarray   # [calls] int64: a call's cold ids, whole blocks
    starts: np.ndarray    # [calls, tiles] int32: where a tile's run begins in its call's
    run: int              # the longest run of a tile (whole groups)
    cold_slots: int       # slots that name a cold row


def tiles_of(n: int, tile: int = TILE) -> int:
    return -(-n // tile)


def ranks(hot_ids: np.ndarray, rows: int) -> np.ndarray:
    """``[rows]`` int32: a hot row's place in ``hot_ids``, -1 for a cold
    row (a row ``hot_ids`` lists twice takes one of its places)."""
    rank = np.full(rows, -1, np.int32)
    rank[hot_ids] = np.arange(hot_ids.size, dtype=np.int32)
    return rank


def localize(ids: np.ndarray, rank: np.ndarray, hot: int, loc: np.ndarray,
             tile: int = TILE, block: int = BLOCK) -> Local:
    """The kernel's local indices of ``ids [calls, n]`` int32 (rows of the
    table :func:`ranks` ``rank`` is of, ``hot`` hot rows) written into ``loc
    [calls, n]``;
    and the calls' cold ids, a call's padded to whole blocks of
    ``block``. The padding names the table's rows one after another, not
    one row: XLA's gather reads a row named again and again three times
    slower than a row's neighbour (``tools/als_fetch_probe.py``,
    ``cold_list``)."""
    calls, n = ids.shape
    tiles = tiles_of(n, tile)
    rank.take(ids, out=loc, mode="clip")
    is_cold = loc < 0
    if n != tiles * tile:                  # the last tile's end: no slot, so not cold
        whole = np.zeros((calls, tiles * tile), bool)
        whole[:, :n] = is_cold
        is_cold = whole
    place = np.cumsum(is_cold.reshape(calls * tiles, tile), axis=1, dtype=np.int32)
    run = (place[:, -1] + (GROUP - 1)) & -GROUP       # a tile's, whole groups
    ends = np.cumsum(run.reshape(calls, tiles), axis=1, dtype=np.int32)
    starts = ends - run.reshape(calls, tiles)
    lengths = -(-ends[:, -1].astype(np.int64) // block) * block
    first = np.cumsum(lengths) - lengths              # a call's in ``cold``
    cold = np.arange(int(lengths.sum()), dtype=np.int32) % np.int32(rank.size)
    # The cold slots, by their place in the whole tiles: ``k`` counts from 1.
    at = np.flatnonzero(is_cold)
    k = place.reshape(-1)[at]
    in_tile = at // tile
    call = in_tile // tiles
    at -= call * (tiles * tile - n)                   # their place in ``ids``
    cold[first[call] + starts.reshape(-1)[in_tile] + (k - 1)] = ids.reshape(-1)[at]
    loc.reshape(-1)[at] = (hot - 1) + (in_tile % tiles % 2) * tile + k
    return Local(cold, lengths, starts, int(run.max()) if run.size else 0, int(at.size))


def cold_rows(n: int, cap: int, tile: int = TILE, block: int = BLOCK) -> int:
    """Rows the buffer of a call's fetched cold rows holds: every slot's
    (each tile's run whole groups, the call's whole blocks), and a run
    more for the last tile's DMA."""
    return -(-(n + GROUP * tiles_of(n, tile)) // block) * block + cap


def fetch_cold(table, cold, first, blocks, into, block: int = BLOCK):
    """``into`` with the rows of ``table`` that ``cold[first:first + blocks
    * block]`` names written from its row 0: XLA's gather a block, as many
    blocks as the call has (a traced count: the program's shapes are the
    same whatever the table's cold ids)."""
    import jax

    def one(j, into):
        ids = jax.lax.dynamic_slice(cold, (first + j * block,), (block,))
        return jax.lax.dynamic_update_slice(
            into, table.at[ids].get(mode="promise_in_bounds"), (j * block, 0))

    return jax.lax.fori_loop(0, blocks, one, into)


def _copy_group(loc_ref, rows, out_ref, at):
    """Slots ``[at, at + GROUP)`` of the tile: a row read, a row written."""
    from jax.experimental import pallas as pl

    for j in range(GROUP):                                        # unrolled
        out_ref[pl.ds(at + j, 1), :] = rows[pl.ds(loc_ref[at + j], 1), :]


def _body(starts_ref, loc_ref, hot_ref, cold_ref, out_ref, rows, hot_sem, cold_sem,
          *, n: int, hot: int, cap: int, unroll: int):
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile = loc_ref.shape[0]
    t, tiles = pl.program_id(0), pl.num_programs(0)

    def run_of(step):
        """The DMA of tile ``step``'s run of cold rows into its half."""
        half = pl.multiple_of(hot + (step % 2) * tile, GROUP)
        return pltpu.make_async_copy(
            cold_ref.at[pl.ds(pl.multiple_of(starts_ref[step], GROUP), cap)],
            rows.at[pl.ds(half, cap)], cold_sem.at[step % 2])

    @pl.when(t == 0)
    def _():
        hot_rows = pltpu.make_async_copy(hot_ref, rows.at[pl.ds(0, hot)], hot_sem)
        hot_rows.start()
        run_of(0).start()
        hot_rows.wait()

    @pl.when(t + 1 < tiles)
    def _():
        run_of(t + 1).start()

    run_of(t).wait()

    # ``unroll`` slots a turn, a group traced once and unrolled where the
    # loop is lowered: traced thirty-two slots long, a side's call sites
    # took 9 s of a fit's first dispatch on the chip's host.
    def slots(i, carry):
        def group(g, carry):
            _copy_group(loc_ref, rows, out_ref,
                        pl.multiple_of(i * unroll + g * GROUP, GROUP))
            return carry

        return jax.lax.fori_loop(0, unroll // GROUP, group, carry, unroll=True)

    # Whole tiles: past a call's end ``loc`` is padding (row 0) and the
    # output block's rows are dropped where it is written back.
    jax.lax.fori_loop(0, min(tile, n) // unroll, slots, 0)


def fetch(loc, starts, hot, cold, *, cap: int, tile: int = TILE,
          unroll: Optional[int] = None, interpret: Optional[bool] = None):
    """``Y [n, lanes]``: row ``loc[j]`` of (``hot [hot rows, lanes]``
    followed by the slot's tile's run of ``cold``) for every slot ``j``
    (:func:`localize`'s ``loc [n]`` and ``starts [tiles]``; ``cold
    [rows, lanes]`` the fetched cold rows, ``cap`` rows of it readable
    from every tile's start; ``n`` whole groups of eight). ``unroll`` is
    the probe's: else :data:`UNROLL` slots a turn of the loop where ``n``
    is whole turns of them, half as many, or a group."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from flinkml_tpu.kernels import _mosaic

    if interpret is None:
        interpret = _mosaic.interpret_mode()
    (n,), (hot_n, lanes) = loc.shape, hot.shape
    tiles = tiles_of(n, tile)
    if unroll is None:
        unroll = next((u for u in (UNROLL, UNROLL // 2) if n % u == 0), GROUP)
    if n % unroll or starts.shape != (tiles,) or cap % GROUP or cap > tile:
        raise ValueError(
            f"fetch wants whole groups of {unroll} slots, a start a tile of {tile} "
            f"and a run of whole groups within one, got {n} slots, starts "
            f"{starts.shape}, runs of {cap}")
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_body, n=n, hot=hot_n, cap=cap, unroll=unroll),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(tiles,),
                in_specs=[
                    pl.BlockSpec((tile,), lambda t, starts: (t,),
                                 memory_space=pltpu.SMEM),
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((min(tile, n), lanes),
                                       lambda t, starts: (t, 0)),
                scratch_shapes=[
                    pltpu.VMEM((hot_n + 2 * tile, lanes), jnp.float32),
                    pltpu.SemaphoreType.DMA(()),
                    pltpu.SemaphoreType.DMA((2,))]),
            out_shape=_mosaic.out_struct((n, lanes), hot.dtype, loc, hot, cold),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(starts.astype(jnp.int32),
          jnp.pad(loc.astype(jnp.int32), (0, tiles * tile - n)), hot, cold)
