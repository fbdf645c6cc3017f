"""Device readings behind ``kernels/row_fetch``: what a chunk of
``als-yahoomusic.fit``'s half-step pays to fetch its slots' fixed-side
rows, form by form, at the cell's own shapes (chunks of 262,144 slots of
512-byte float32 rows; the users' half-step fetches rows of the 624,961
items, drawn by ``datagen_ratings``' Zipf-Mandelbrot law with 2 % flat,
the items' half-step rows of the 1,000,990 users, drawn by their degrees;
:data:`PADDING` of the slots name the zero row, as the ladder's padding
does; a row's id is a seeded permutation's, so the hot rows lie
anywhere).

Prints one JSON line a reading, ns a slot (the host's clock around
``REPS`` calls of one jitted ``lax.map`` over :data:`CHUNKS` chunks, each
waited for; the least):

1. ``gather``: XLA's gather of every slot as the program has it, its
   rows summed a target (the fetch alone) and through the program's own
   product at ``HIGHEST`` (``systems``);
2. ``kernel``: :func:`~flinkml_tpu.kernels.row_fetch.fetch` alone, the
   cold rows fetched beforehand and handed over, by hot rows, tile and
   unroll, with the slots as drawn and with every slot hot; its rows
   against the gather's to the bit; and ``assembled``: the same kernel
   with eight rows put together in registers and stored as one group (a
   form the kernel does not ship);
3. ``whole``: the cold slots' gather (a block of 4,096 ids at a time
   into the loop's one buffer) and the kernel together, as the program
   runs them, against 1.'s two readings, by hot rows;
4. ``call``: one call of one tile, with 65,536 hot rows and with eight:
   what a ``pallas_call`` pays to copy the hot rows in (us a call);
5. ``compile``: seconds to compile one program of eight call sites of
   eight chunk lengths (one kernel body, eight grids);
6. ``cold_list``: XLA's gather over a chunk's cold ids alone (65,536 hot
   rows) as the list grows by padding: one row again and again, rows one
   after another, the hottest of the cold rows (ns a row: the gather's
   rate is not one number, and a row named again and again is its worst).

Run it through the chip tool: ``python tools/als_fetch_probe.py [seed
[sections]]`` (``sections`` a string of the numbers above, all six by
default; about five minutes).
"""

import functools
import json
import os
import sys
import time
from unittest import mock

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

USERS, ITEMS, RATINGS, RANK = 1_000_990, 624_961, 252_800_275, 100
SLOTS, CHUNKS, TARGETS, REPS = 1 << 18, 32, 1024, 3
PADDING = 0.172


def draw(seed: int, side: str):
    """``(ids [CHUNKS, SLOTS] int32, order [rows + 1])``: the fixed-side
    rows the slots of ``side``'s half-step name (the zero row last) and
    the rows by falling degree under the law, the zero row first."""
    from benchmark import datagen_ratings as laws

    rng = np.random.default_rng(seed)
    n = CHUNKS * SLOTS
    if side == "users":          # the fixed side is the items
        rows = ITEMS
        ranks = laws._item_ranks(rng.random(n), rows)
        flat = rng.random(n) < laws.ITEM_FLAT
        ranks[flat] = rng.integers(0, rows, int(flat.sum()), dtype=np.int32)
    else:
        rows = USERS
        held = np.cumsum(laws.user_degrees(rows, RATINGS))
        ranks = np.searchsorted(held, rng.random(n) * held[-1]).astype(np.int32)
    of_rank = rng.permutation(rows).astype(np.int32)
    ids = of_rank[ranks]
    ids[rng.random(n) < PADDING] = rows
    return ids.reshape(CHUNKS, SLOTS), np.concatenate([[rows], of_rank]).astype(np.int32)


def _assembled(loc_ref, rows, out_ref, at):
    """``row_fetch._copy_group`` with the eight rows put together and
    stored at once."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    out_ref[pl.ds(at, 8), :] = jnp.concatenate(
        [rows[pl.ds(loc_ref[at + j], 1), :] for j in range(8)], axis=0)


def main(seed: int, sections: str):
    import jax
    import jax.numpy as jnp

    from flinkml_tpu.kernels import row_fetch

    if jax.default_backend() != "tpu":
        raise SystemExit(f"backend {jax.default_backend()}: the readings are a chip's")
    lines = []

    def emit(**line):
        lines.append(line)
        print(json.dumps(line), flush=True)

    def timed(run, *args):
        out = jax.block_until_ready(run(*args))                      # compiles
        took = []
        for _ in range(REPS):
            start = time.perf_counter()
            jax.block_until_ready(run(*args))
            took.append(time.perf_counter() - start)
        return min(took), out

    def summed(y):
        return jnp.sum(y.reshape(TARGETS, SLOTS // TARGETS, row_fetch.LANES), axis=1)

    def systems(y):
        y = y.reshape(TARGETS, SLOTS // TARGETS, row_fetch.LANES)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, row_fetch.LANES), 2)
        right = jnp.where(lane == RANK, 1.0, y)
        g = jnp.einsum("clk,clm->ckm", y, right, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        return g[:, :RANK, :RANK + 4]

    def by_gather(then):
        return jax.jit(lambda fixed, ids: jax.lax.map(
            lambda i: then(fixed.at[i].get(mode="promise_in_bounds")), ids))

    def by_kernel(then, cap, tile, unroll, gathers=True):
        """``(fixed, hot ids, loc, starts, cold, first, blocks)``: the
        chunks' rows through the kernel, a chunk's cold rows gathered a
        block at a time into the one buffer the loop hands on (or, for
        the kernel alone, the buffer as it is)."""
        def chunk(fixed, hot, cold, held, operands):
            loc, starts, first, blocks = operands
            if gathers:
                held = row_fetch.fetch_cold(fixed, cold, first, blocks, held)
            return held, then(row_fetch.fetch(loc, starts, hot, held, cap=cap, tile=tile,
                                              unroll=unroll))

        def run(fixed, hot_ids, loc, starts, cold, first, blocks):
            held = jnp.zeros((row_fetch.cold_rows(loc.shape[1], cap, tile),
                              row_fetch.LANES), jnp.float32)
            return jax.lax.scan(
                functools.partial(chunk, fixed, fixed.at[hot_ids].get(), cold), held,
                (loc, starts, first, blocks))[1]

        return jax.jit(run)

    def hottest(order, hot):
        """The ``hot`` hot rows' ids (a short table's filled with the zero
        row)."""
        return jnp.asarray(np.concatenate(
            [order[:hot], np.full(max(0, hot - order.size), order.size - 1, np.int32)]))

    def localized(ids, order, hot, tile):
        """``(layout, a tile's DMA, (loc, starts, cold, first, blocks))``."""
        loc = np.empty_like(ids)
        local = row_fetch.localize(
            ids, row_fetch.ranks(np.asarray(hottest(order, hot)), order.size), hot, loc,
            tile)
        cap = max(row_fetch.GROUP, 1 << (local.run - 1).bit_length())
        first = np.cumsum(local.lengths) - local.lengths
        cold = np.concatenate([local.cold, np.zeros(row_fetch.BLOCK, np.int32)])  # never empty
        return local, cap, (
            jnp.asarray(loc), jnp.asarray(local.starts), jnp.asarray(cold),
            jnp.asarray(first.astype(np.int32)),
            jnp.asarray((local.lengths // row_fetch.BLOCK).astype(np.int32)))

    def gathered(lists):
        """ns a row of XLA's gather over ``lists [chunks, rows]``."""
        seconds, _ = timed(jax.jit(lambda fixed, cold: jax.lax.map(
            lambda i: jnp.sum(fixed.at[i].get(mode="promise_in_bounds"), axis=0), cold)),
            fixed, jnp.asarray(lists))
        return seconds / lists.size * 1e9

    for side in ("users", "items"):
        ids, order = draw(seed, side)
        rows = order.size
        fixed = jax.random.normal(jax.random.PRNGKey(seed % (1 << 31)),
                                  (rows, row_fetch.LANES), jnp.float32)
        fixed = fixed.at[rows - 1].set(0.0)
        ids_d = jnp.asarray(ids)
        slots = ids.size
        base = {}
        if "1" in sections or "3" in sections:
            for name, then in (("summed", summed), ("systems", systems)):
                seconds, _ = timed(by_gather(then), fixed, ids_d)
                base[name] = seconds / slots * 1e9
                emit(reading="gather", side=side, then=name, ns_a_slot=base[name])
        if "2" in sections:
            for hot, tile, unroll in ((65536, 2048, 8), (65536, 1024, 8), (65536, 4096, 8),
                                      (65536, 2048, 16), (65536, 2048, 32),
                                      (16384, 2048, 8), (4096, 2048, 8)):
                local, cap, operands = localized(ids, order, hot, tile)
                hot_ids = hottest(order, hot)
                seconds, _ = timed(by_kernel(summed, cap, tile, unroll, False),
                                   fixed, hot_ids, *operands)
                shape = dict(side=side, hot=hot, tile=tile, unroll=unroll, cap=cap,
                             cold_slot_share=local.cold_slots / slots,
                             cold_rows_fetched_share=local.cold.size / slots)
                emit(reading="kernel", ns_a_slot=seconds / slots * 1e9, **shape)
                if (hot, tile, unroll) == (65536, 2048, 8):
                    def floats_off():
                        """Two chunks' rows, against the gather's."""
                        off = by_kernel(lambda y: y, cap, tile, unroll)
                        one = jax.jit(lambda fixed, ids, *operands: jnp.sum(
                            off(fixed, *operands)[0]
                            != fixed.at[ids].get(mode="promise_in_bounds")))
                        loc, starts, cold, first, blocks = operands
                        return sum(int(one(
                            fixed, ids_d[c], hot_ids, loc[c:c + 1], starts[c:c + 1], cold,
                            first[c:c + 1], blocks[c:c + 1])) for c in range(2))

                    emit(reading="bits", side=side, floats=2 * SLOTS * row_fetch.LANES,
                         floats_off=floats_off())
                    seconds, _ = timed(by_kernel(summed, cap, tile, unroll, False), fixed,
                                       hot_ids, operands[0] % hot, *operands[1:])
                    emit(reading="kernel", every_slot="hot",
                         ns_a_slot=seconds / slots * 1e9, **shape)
                    with mock.patch.object(row_fetch, "_copy_group", _assembled):
                        try:
                            emit(reading="bits", form="assembled", side=side,
                                 floats_off=floats_off())
                            seconds, _ = timed(
                                by_kernel(summed, cap, tile, unroll, False),
                                fixed, hot_ids, *operands)
                            emit(reading="kernel", form="assembled",
                                 ns_a_slot=seconds / slots * 1e9, **shape)
                        except Exception as refused:  # Mosaic's word, kept
                            emit(reading="kernel", form="assembled",
                                 refused=str(refused)[:400])
        if "3" in sections:
            for hot in (65536, 32768, 16384, 8192):
                local, cap, operands = localized(ids, order, hot, row_fetch.TILE)
                hot_ids = hottest(order, hot)
                for name, then in (("summed", summed), ("systems", systems)):
                    seconds, _ = timed(by_kernel(then, cap, row_fetch.TILE, None),
                                       fixed, hot_ids, *operands)
                    emit(reading="whole", side=side, then=name, hot=hot, cap=cap,
                         cold_slot_share=local.cold_slots / slots,
                         cold_rows_fetched_share=local.cold.size / slots,
                         ns_a_slot=seconds / slots * 1e9, gather_ns_a_slot=base[name])
        if "4" in sections and side == "users":
            for hot in (65536, 8):
                local, cap, operands = localized(
                    ids[:, :row_fetch.TILE], order, hot, row_fetch.TILE)
                seconds, _ = timed(
                    by_kernel(lambda y: jnp.sum(y, axis=0), cap, row_fetch.TILE, None,
                              False),
                    fixed, hottest(order, hot), *operands)
                emit(reading="call", hot=hot, slots=row_fetch.TILE,
                     us_a_call=seconds / CHUNKS * 1e6)
        if "5" in sections and side == "users":
            local, cap, operands = localized(ids[:1], order, 65536, row_fetch.TILE)
            lengths = [SLOTS - 256 * k for k in range(8)]

            def sites(fixed, hot_ids, loc, starts, cold, first, blocks):
                hot = fixed.at[hot_ids].get()
                held = row_fetch.fetch_cold(
                    fixed, cold, first[0], blocks[0],
                    jnp.zeros((row_fetch.cold_rows(SLOTS, cap), row_fetch.LANES),
                              jnp.float32))
                return sum(jnp.sum(row_fetch.fetch(
                    loc[0, :n], starts[0, :row_fetch.tiles_of(n)], hot, held, cap=cap))
                    for n in lengths)

            args = (fixed, hottest(order, 65536), *operands)
            start = time.perf_counter()
            jax.jit(sites).lower(*args).compile()
            emit(reading="compile", call_sites=len(lengths),
                 seconds=time.perf_counter() - start)
        if "6" in sections:
            local, cap, operands = localized(ids, order, 65536, row_fetch.TILE)
            first = np.cumsum(local.lengths) - local.lengths
            most = int(local.lengths.min())
            if not most:                   # a rehearsal's table: no cold row
                continue
            listed = np.stack([local.cold[at:at + most] for at in first])
            emit(reading="cold_list", side=side, list="as laid", rows_a_chunk=most,
                 ns_a_row=gathered(listed))
            warm = order[65536:65536 + 16384] if order.size > 65536 else order[-1:]
            for grown in (1.25, 1.5, 2.0):
                more = int(most * (grown - 1)) & -8
                again = np.random.default_rng(seed + 6).choice(warm, (CHUNKS, more))
                for name, padding in (
                        ("one row", np.full((CHUNKS, more), rows - 1)),
                        ("rows one after another", np.tile(np.arange(more) % rows,
                                                           (CHUNKS, 1))),
                        ("warm rows", again)):
                    both = np.concatenate([listed, padding.astype(np.int32)], axis=1)
                    ns = gathered(both)
                    emit(reading="cold_list", side=side, list=f"{name} x {grown}",
                         rows_a_chunk=both.shape[1], ns_a_row=ns,
                         ns_a_first_row=ns * both.shape[1] / most)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/als_fetch_probe.jsonl", "w") as out:
        out.writelines(json.dumps(line) + "\n" for line in lines)


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 0,
         sys.argv[2] if len(sys.argv) > 2 else "123456")
