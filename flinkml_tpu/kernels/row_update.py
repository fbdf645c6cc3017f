"""Pallas sorted row update — ``table.at[ids].add(rows)`` on ids in sorted
order, each distinct GROUP of eight rows read, added to and written back
once, many groups in flight.

XLA's scatter-add of rows is a read-modify-write a NAMED row, one after
another because ids may repeat: 100 ns a row of 1.5 KB on a v5e whatever
the row holds (PERF.md section 5: 62.6 % of ``w2v-1bw.fit``'s step),
where the same program FETCHES those rows at 13.8 ns. Nothing in a
synchronous step asks for that order: every gradient is taken at the
step's start and rows that collide are summed. So the caller sorts the
step's (id, contribution) entries by id (a stable sort: one fixed order)
and here a table row is touched once, however often it is named.

*The unit is a group of eight rows.* Mosaic refuses a DMA of one row of
a float32 ``[rows, lanes]`` table in HBM ("Slice shape along dimension 0
must be aligned to tiling (8)"): what moves is the aligned group ``id //
8``, ``8 x lanes`` contiguous floats (three ``(8, 128)`` tiles at 384
lanes, 12 KB), and an entry is added into sublane ``id % 8`` of it. The
table's rows are therefore a multiple of eight
(:func:`unsupported_reason`).

*A grid step is a tile of sorted entries* (:data:`TILE`; the ids in SMEM,
the contributions streamed by a ``BlockSpec``). A scalar pass lists the
tile's distinct groups and where each one's run of entries starts; then
the walk, a CHUNK of :data:`CHUNK` groups at a time over a ring of
:data:`RING` chunks of buffers in VMEM: the reads of the next ``RING - 1``
chunks are already in flight, the chunk's reads are waited for (they
signal ONE semaphore, and one wait takes the bytes of all of them: the
scalar core issues every DMA and every add and its time is the kernel's;
a start and a wait a group each way read 56 ns a group on a v5e, chunks
of 16 read 41), the chunk's entries are added left to right by the
vector unit in float32, each into its row of the ring as the scalar pass
listed it, the chunk's writes are started. A chunk's buffers are read
into again only when its writes have landed, and all writes have landed
when a tile ends: a group that spans two tiles is read again after its
write (a TPU's grid steps run in order).

*The same bits as the scatter-add on the sorted list*: a row's entries
are added to the row one after another, in the list's order, in float32.
The table is aliased in and out and never copied.

Traced in 32-bit mode whatever the caller's (PR 30: a 64-bit block
aborts the process in Mosaic).
"""

from __future__ import annotations

import functools
from typing import Optional

#: Lanes of a vreg: a table's row is whole rows of them.
LANES = 128
#: Rows a DMA moves: the sublanes of a float32 tile.
GROUP = 8
#: Sorted entries a grid step holds (half of it where that holds them
#: all): a multiple of 1,024, as the chip tiles a vector of int32.
TILE = 2048
#: Groups a chunk (its reads share a semaphore and one wait, its writes
#: another) and chunks of buffers in fast memory (a power of two): the
#: reads run ``RING - 1`` chunks ahead of the walk.
CHUNK, RING = 16, 4


def unsupported_reason(dtype, rows: int, lanes: int,
                       devices: int = 1) -> Optional[str]:
    """Why the kernel does not take this table (None = it does): read off
    the backend and what the update is handed, nothing else."""
    import jax.numpy as jnp

    from flinkml_tpu.kernels import _mosaic

    if _mosaic.interpret_mode():
        return "not a TPU: Mosaic's kernel would run interpreted"
    if devices != 1:
        return (f"{devices} devices: the table is row-sharded and its "
                "updates are the embedding exchange's")
    if jnp.dtype(dtype) != jnp.float32:
        return f"a {jnp.dtype(dtype).name} table: the sums are float32's"
    if lanes % LANES:
        return (f"rows of {lanes} floats: the chip lays such a table with "
                "its rows along the lanes, and a group is no slice of it")
    if rows % GROUP:
        return (f"{rows} rows: a DMA moves an aligned group of {GROUP}, and "
                "the last group ends past the table")
    return None


def _body(ids_ref, rows_ref, table_ref, out_ref, buf, read_sem, write_sem,
          groups, first, place, *, entries: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del table_ref       # aliased: the rows are read where they are written
    tile = rows_ref.shape[0]
    count = jnp.minimum(tile, entries - pl.program_id(0) * tile)

    # The tile's distinct groups (their first rows), where each one's run
    # of entries starts, and an entry's row in the ring of buffers. No
    # branch: an entry writes where the NEXT group will be listed, and
    # only a new group moves that place on.
    def list_groups(e8, carry):
        k, last = carry
        for j in range(GROUP):                              # unrolled
            e = e8 * GROUP + j
            row = ids_ref[e]
            group = row & -GROUP
            groups[k] = group
            first[k] = e
            new = jnp.logical_and(group != last, e < count)
            k, last = k + new.astype(jnp.int32), jnp.where(new, group, last)
            place[e] = (((k - 1) & (RING * CHUNK - 1)) * GROUP
                        + (row & (GROUP - 1)))
        return k, last

    n_groups, last = jax.lax.fori_loop(
        0, tile // GROUP, list_groups, (jnp.int32(0), jnp.int32(-1)))
    # Past the last group, a chunk's worth of empty runs of a group that
    # is there: read with the chunk, added to by nothing, never written.
    for j in range(CHUNK + 1):
        groups[n_groups + j] = last
        first[n_groups + j] = count
    n_chunks = (n_groups + CHUNK - 1) // CHUNK

    def in_table(k):
        return out_ref.at[pl.ds(pl.multiple_of(groups[k], GROUP), GROUP)]

    def in_ring(slot, groups_held=1):
        at = pl.multiple_of(slot * GROUP, GROUP)
        return buf.at[pl.ds(at, groups_held * GROUP)]

    def start_reads(c):
        ring = c & (RING - 1)
        for j in range(CHUNK):
            pltpu.make_async_copy(in_table(c * CHUNK + j),
                                  in_ring(ring * CHUNK + j),
                                  read_sem.at[ring]).start()

    def write(c, j):
        ring = c & (RING - 1)
        return pltpu.make_async_copy(in_ring(ring * CHUNK + j),
                                     in_table(c * CHUNK + j),
                                     write_sem.at[ring])

    def wait_for_chunk(sem):
        """A chunk's DMAs signal one semaphore: one wait for the bytes of
        all of them."""
        pltpu.make_async_copy(out_ref.at[pl.ds(0, CHUNK * GROUP)],
                              in_ring(0, CHUNK), sem).wait()

    def add(e, carry):
        at = pl.ds(place[e], 1)
        buf[at, :] = buf[at, :] + rows_ref[pl.ds(e, 1), :]
        return carry

    def one_chunk(c, carry):
        ahead = c + RING - 1

        @pl.when(ahead < n_chunks)
        def _():
            # its buffers are those of the chunk before ``c``
            @pl.when(c > 0)
            def _():
                wait_for_chunk(write_sem.at[ahead & (RING - 1)])

            start_reads(ahead)

        @pl.when(c >= 0)
        def _():
            wait_for_chunk(read_sem.at[c & (RING - 1)])
            jax.lax.fori_loop(first[c * CHUNK], first[c * CHUNK + CHUNK], add, 0)

            @pl.when(c < n_chunks - 1)
            def _():
                for j in range(CHUNK):
                    write(c, j).start()

            @pl.when(c == n_chunks - 1)
            def _():
                def start_write(j, carry):
                    write(c, j).start()
                    return carry

                jax.lax.fori_loop(0, n_groups - c * CHUNK, start_write, 0)

        return carry

    # The first ``RING - 1`` turns only start reads.
    jax.lax.fori_loop(1 - RING, n_chunks, one_chunk, 0)

    # The writes not waited for: whole chunks, then the last one's groups.
    def land(c, carry):
        wait_for_chunk(write_sem.at[c & (RING - 1)])
        return carry

    jax.lax.fori_loop(jnp.maximum(n_chunks - RING, 0), n_chunks - 1, land, 0)

    def land_group(j, carry):
        write(n_chunks - 1, 0).wait()
        return carry

    jax.lax.fori_loop(0, n_groups - (n_chunks - 1) * CHUNK, land_group, 0)


def add_rows_sorted(table, ids_sorted, rows_sorted, *,
                    tile: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """``table.at[ids_sorted].add(rows_sorted)`` for ``ids_sorted [n]``
    int32 in rising order (repeats and all) and ``rows_sorted [n,
    lanes]`` float32, ``table [rows, lanes]`` float32 with ``rows`` a
    multiple of :data:`GROUP` and every id below it: the same bits (a
    row's entries added to it left to right). The table is updated where
    it lies: donate it. ``tile`` is a test's (interpreted: runs and
    groups that span a tile's end at small sizes)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from flinkml_tpu.kernels import _mosaic

    if interpret is None:
        interpret = _mosaic.interpret_mode()
    entries, lanes = rows_sorted.shape
    if tile is None:
        tile = TILE if entries > TILE // 2 else TILE // 2
    tiles = -(-entries // tile)
    with jax.enable_x64(False):
        # Whole tiles: what is past the last entry is never walked.
        pad = tiles * tile - entries
        ids = jnp.pad(ids_sorted.astype(jnp.int32), (0, pad))
        rows = jnp.pad(rows_sorted, ((0, pad), (0, 0)))
        return pl.pallas_call(
            functools.partial(_body, entries=entries),
            grid=(tiles,),
            in_specs=[
                pl.BlockSpec((tile,), lambda t: (t,), memory_space=pltpu.SMEM),
                pl.BlockSpec((tile, lanes), lambda t: (t, 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=_mosaic.out_struct(table.shape, table.dtype, table),
            scratch_shapes=[
                pltpu.VMEM((RING * CHUNK * GROUP, lanes), jnp.float32),
                pltpu.SemaphoreType.DMA((RING,)),
                pltpu.SemaphoreType.DMA((RING,)),
                pltpu.SMEM((tile + CHUNK + 1,), jnp.int32),
                pltpu.SMEM((tile + CHUNK + 1,), jnp.int32),
                pltpu.SMEM((tile,), jnp.int32)],
            input_output_aliases={2: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(ids, rows, table)


def sorted_entries(ids, rows):
    """``(ids, rows)`` in rising order of id, by a stable sort of (id,
    position): entries of one row keep their order, so the same entries
    give the same sums bit for bit, and the contributions are fetched in
    that order."""
    import jax
    import jax.numpy as jnp

    with jax.enable_x64(False):
        position = jax.lax.iota(jnp.int32, ids.shape[0])
        ids, position = jax.lax.sort((ids.astype(jnp.int32), position),
                                     num_keys=1, is_stable=True)
        return ids, rows[position]


def add_rows(table, ids, rows):
    """``table.at[ids].add(rows)`` for ids in any order:
    :func:`add_rows_sorted` on their :func:`sorted_entries`."""
    return add_rows_sorted(table, *sorted_entries(ids, rows))
