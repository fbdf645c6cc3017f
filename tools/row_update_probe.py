"""Device readings behind ``kernels/row_update``: what a step of
``w2v-1bw.fit`` pays to add its contributions to their table rows, form
by form, at the cell's own shapes (a ``[1,115,016, 384]`` float32 table;
16,384 centres, 16,384 contexts and 81,920 negatives a step, drawn from
the configuration's laws: Zipf-Mandelbrot ``(r + 1.35)^-1`` over
1,115,011 ranks, subsampling at 1e-4, negatives by ``count^0.75``; a
table row is a word's RANK, so the hot rows are neighbours).

Prints one JSON line a reading, ms a call (``reps`` calls inside one
jitted loop, the table donated, the host's clock around it):

1. XLA's scatter-add as the program has it, then on sorted ids under
   ``indices_are_sorted``, on distinct ids under ``unique_indices``
   (sorted, unsorted, and every entry handed over with all but a run's
   last sent past the table), and as a gather, an add and a scatter-set;
2. a Mosaic kernel that only reads and writes back the distinct 12 KB
   groups: a start and a wait a group with 8 to 64 in flight, then a
   chunk's DMAs on one semaphore with one wait (as ``row_update`` ships
   it), blocks of 16 and 32 rows, and the 4 KB groups of a 128-lane
   table: ns a group against groups in flight and against bytes;
3. ``lax.sort`` of (id, position) pairs and XLA's gather of the
   contributions into sorted order;
4. ``row_update.add_rows_sorted`` at several chunk and ring sizes and
   tiles, with one entry a group (the walk's cost a group) and with one
   group (its cost an entry), and its bits against XLA's scatter-add on
   the sorted list.

Run it through the chip tool: ``python tools/row_update_probe.py [seed
[sections]]`` (``sections`` a string of the numbers above, all four by
default; about three minutes).
"""

import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

VOCAB, ROWS, LANES = 1_115_011, 1_115_016, 384
BATCH, NEGATIVES, REPS = 16_384, 5, 20


def draw(seed: int):
    """A step's ids as the cell's laws give them: ``(centres, contexts
    [BATCH], negatives [BATCH * NEGATIVES])``, int32 ranks."""
    rng = np.random.default_rng(seed)
    share = (np.arange(VOCAB) + 1.35) ** -1.0
    share /= share.sum()
    keep = np.minimum(1.0, np.sqrt(1e-4 / share) + 1e-4 / share)

    def sample(weight, n):
        return np.searchsorted(np.cumsum(weight / weight.sum()),
                               rng.random(n)).clip(0, VOCAB - 1).astype(np.int32)

    return (sample(share * keep, BATCH), sample(share * keep, BATCH),
            sample(share ** 0.75, BATCH * NEGATIVES))


def groups_kernel(table, groups, *, slots: int, tile: int = 2048):
    """Every group of ``groups`` (distinct, int32, whole tiles) read into
    a ring of ``slots`` buffers and written back, ``slots * 3 // 4``
    reads ahead: the DMA's cost alone."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from flinkml_tpu.kernels import _gate

    ahead = slots * 3 // 4

    def body(g_ref, table_ref, out_ref, buf, read_sem, write_sem):
        del table_ref

        def in_table(k):
            return out_ref.at[pl.ds(pl.multiple_of(g_ref[k] * 8, 8), 8)]

        def read(k):
            return pltpu.make_async_copy(in_table(k), buf.at[k % slots],
                                         read_sem.at[k % slots])

        def write(k):
            return pltpu.make_async_copy(buf.at[k % slots], in_table(k),
                                         write_sem.at[k % slots])

        jax.lax.fori_loop(0, ahead, lambda k, c: (read(k).start(), c)[1], 0)

        def one(k, c):
            @pl.when(k + ahead < tile)
            def _():
                @pl.when(k + ahead >= slots)
                def _():
                    write(k + ahead - slots).wait()

                read(k + ahead).start()

            read(k).wait()
            write(k).start()
            return c

        jax.lax.fori_loop(0, tile, one, 0)
        jax.lax.fori_loop(tile - slots, tile,
                          lambda k, c: (write(k).wait(), c)[1], 0)

    return pl.pallas_call(
        body, grid=(groups.shape[0] // tile,),
        in_specs=[pl.BlockSpec((tile,), lambda t: (t,), memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        scratch_shapes=[pltpu.VMEM((slots, 8, table.shape[1]), jnp.float32),
                        pltpu.SemaphoreType.DMA((slots,)),
                        pltpu.SemaphoreType.DMA((slots,))],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=_gate.interpret_mode(),
    )(groups, table)


def groups_kernel_chunked(table, groups, *, chunk: int, ring: int,
                          rows: int = 8, tile: int = 2048):
    """As :func:`groups_kernel` over aligned blocks of ``rows`` table rows,
    ``chunk`` DMAs a semaphore and ONE wait for all of them, ``ring``
    chunks of buffers (reads ``ring - 1`` chunks ahead): the form
    ``row_update`` ships."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from flinkml_tpu.kernels import _gate

    chunks = tile // chunk

    def body(g_ref, table_ref, out_ref, buf, read_sem, write_sem):
        del table_ref

        def in_table(k):
            return out_ref.at[pl.ds(pl.multiple_of(g_ref[k] * rows, rows), rows)]

        def in_ring(slot, held=1):
            return buf.at[pl.ds(pl.multiple_of(slot * rows, rows), held * rows)]

        def copies(c, back):
            at = (c % ring) * chunk
            for j in range(chunk):
                here, there = in_ring(at + j), in_table(c * chunk + j)
                if back:
                    pltpu.make_async_copy(here, there, write_sem.at[c % ring]).start()
                else:
                    pltpu.make_async_copy(there, here, read_sem.at[c % ring]).start()

        def wait(sem):
            pltpu.make_async_copy(out_ref.at[pl.ds(0, chunk * rows)],
                                  in_ring(0, chunk), sem).wait()

        for c in range(ring - 1):
            copies(c, False)

        def one(c, carry):
            @pl.when(c + ring - 1 < chunks)
            def _():
                @pl.when(c > 0)
                def _():
                    wait(write_sem.at[(c - 1) % ring])

                copies(c + ring - 1, False)

            wait(read_sem.at[c % ring])
            copies(c, True)
            return carry

        jax.lax.fori_loop(0, chunks, one, 0)
        jax.lax.fori_loop(chunks - ring, chunks,
                          lambda c, carry: (wait(write_sem.at[c % ring]), carry)[1], 0)

    return pl.pallas_call(
        body, grid=(groups.shape[0] // tile,),
        in_specs=[pl.BlockSpec((tile,), lambda t: (t,), memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        scratch_shapes=[pltpu.VMEM((ring * chunk * rows, table.shape[1]), jnp.float32),
                        pltpu.SemaphoreType.DMA((ring,)),
                        pltpu.SemaphoreType.DMA((ring,))],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=_gate.interpret_mode(),
    )(groups, table)


def main():
    import jax
    import jax.numpy as jnp

    from flinkml_tpu.kernels import row_update

    out = {"device": jax.devices()[0].device_kind, "reps": REPS}
    readings = []

    def say(name, ms, **more):
        readings.append({"reading": name, "ms_a_call": ms, **more})
        print(json.dumps(readings[-1]), flush=True)

    table = jnp.zeros((ROWS, LANES), jnp.float32)

    def timed(name, update, *operands, **more):
        """``update(table, *operands) -> table``, REPS times in one
        program."""
        nonlocal table

        @functools.partial(jax.jit, donate_argnums=0)
        def run(table, *operands):
            return jax.lax.fori_loop(
                0, REPS, lambda i, t: update(t, *operands), table)

        try:
            table = jax.block_until_ready(run(table, *operands))
            t0 = time.perf_counter()
            table = jax.block_until_ready(run(table, *operands))
            say(name, (time.perf_counter() - t0) / REPS * 1e3, **more)
        except Exception as e:  # a form the chip's compiler refuses
            say(name, None, error=f"{type(e).__name__}: {e}"[:600], **more)
            table = jnp.zeros((ROWS, LANES), jnp.float32)

    def checksum(name, make, *operands, **more):
        """``make(i, *operands) -> arrays`` whose every element is summed,
        REPS times in one program."""
        @jax.jit
        def run(*operands):
            def body(i, acc):
                return acc + sum(jnp.sum(a.astype(jnp.float32))
                                 for a in make(i, *operands))
            return jax.lax.fori_loop(0, REPS, body, jnp.float32(0))

        jax.block_until_ready(run(*operands))
        t0 = time.perf_counter()
        jax.block_until_ready(run(*operands))
        say(name, (time.perf_counter() - t0) / REPS * 1e3, **more)

    centres, contexts, negatives = draw(int(sys.argv[1]) if len(sys.argv) > 1 else 0)
    sections = sys.argv[2] if len(sys.argv) > 2 else "1234"
    both = np.concatenate([contexts, negatives])
    rng = np.random.default_rng(1)
    lists = {"centres": centres, "negatives": negatives, "u": both}
    for name, ids in lists.items():
        out[f"{name}.entries"] = int(ids.size)
        out[f"{name}.distinct_rows"] = int(np.unique(ids).size)
        out[f"{name}.distinct_groups"] = int(np.unique(ids // 8).size)
        out[f"{name}.hottest_row"] = int(np.bincount(ids).max())
        out[f"{name}.hottest_group"] = int(np.bincount(ids // 8).max())
    print(json.dumps(out), flush=True)

    # 1. XLA's scatter-add: as the program has it, sorted, distinct.
    def section_1():
        for name, ids in lists.items():
            rows = jnp.asarray(rng.normal(size=(ids.size, LANES)).astype(np.float32) * 1e-3)
            timed(f"xla.scatter_add.{name}", lambda t, i, r: t.at[i].add(r),
                  jnp.asarray(ids), rows)
            timed(f"xla.scatter_add.{name}.sorted_flag",
                  lambda t, i, r: t.at[i].add(r, indices_are_sorted=True),
                  jnp.asarray(np.sort(ids)), rows)
            distinct = np.unique(ids)
            timed(f"xla.scatter_add.{name}.sorted_unique_flags",
                  lambda t, i, r: t.at[i].add(r, indices_are_sorted=True,
                                              unique_indices=True),
                  jnp.asarray(distinct), rows[:distinct.size], entries=int(distinct.size))
            timed(f"xla.scatter_add.{name}.unique_flag",
                  lambda t, i, r: t.at[i].add(r, unique_indices=True),
                  jnp.asarray(rng.permutation(distinct)), rows[:distinct.size],
                  entries=int(distinct.size))
            # every entry handed over, all but a run's last sent past the table
            ends = np.sort(ids)
            ends[:-1][ends[:-1] == ends[1:]] = ROWS
            timed(f"xla.scatter_add.{name}.unique_flag_others_dropped",
                  lambda t, i, r: t.at[i].add(r, unique_indices=True, mode="drop"),
                  jnp.asarray(ends), rows, entries=int(ids.size))
            timed(f"xla.gather_add_scatter_set.{name}.sorted_unique_flags",
                  lambda t, i, r: t.at[i].set(t[i] + r, indices_are_sorted=True,
                                              unique_indices=True),
                  jnp.asarray(distinct), rows[:distinct.size], entries=int(distinct.size))


    # 2. a group's cost by DMA, against groups in flight.
    def section_2():
        nonlocal table
        groups = np.unique(both // 8)
        whole = groups[:groups.size // 2048 * 2048].astype(np.int32)
        for slots in (8, 16, 32, 64):
            timed(f"dma.groups_read_written.{slots}_in_flight",
                  functools.partial(groups_kernel, slots=slots), jnp.asarray(whole),
                  groups=int(whole.size))
        spread = (np.arange(whole.size, dtype=np.int32) * 61) % (ROWS // 8)
        timed("dma.groups_read_written.32_in_flight.unsorted",
              functools.partial(groups_kernel, slots=32), jnp.asarray(spread),
              groups=int(whole.size))
        for chunk, ring in ((8, 4), (8, 8), (16, 4), (4, 8), (32, 2), (32, 4)):
            timed(f"dma.groups_read_written.chunks_of_{chunk}_ring_{ring}",
                  functools.partial(groups_kernel_chunked, chunk=chunk, ring=ring),
                  jnp.asarray(whole), groups=int(whole.size))
        # the DMA's cost against its size: blocks of 16 and 32 rows (24, 48 KB),
        # and 4 KB groups of a 128-lane table (ALS's 512-byte rows: ROADMAP B11)
        for rows in (16, 32):
            blocks = np.unique(both // rows)
            blocks = blocks[:blocks.size // 2048 * 2048].astype(np.int32)
            timed(f"dma.blocks_of_{rows}_rows_read_written.chunks_of_8_ring_4",
                  functools.partial(groups_kernel_chunked, chunk=8, ring=4, rows=rows),
                  jnp.asarray(blocks), groups=int(blocks.size))
        wide, table = table, jnp.zeros((ROWS, 128), jnp.float32)
        timed("dma.groups_of_128_lanes_read_written.chunks_of_8_ring_4",
              functools.partial(groups_kernel_chunked, chunk=8, ring=4),
              jnp.asarray(whole), groups=int(whole.size))
        timed("dma.groups_of_128_lanes_read_written.32_in_flight",
              functools.partial(groups_kernel, slots=32), jnp.asarray(whole),
              groups=int(whole.size))
        table = wide
        del wide


    # 3. the sort, and the contributions put in sorted order.
    def section_3():
        def sort(i, ids):
            position = jax.lax.iota(jnp.int32, ids.shape[0])
            return jax.lax.sort((ids + i, position), num_keys=1, is_stable=True)

        for name in ("centres", "u"):
            ids = jnp.asarray(lists[name])
            checksum(f"xla.sort.{name}", sort, ids, entries=int(ids.size))
            rows = jnp.asarray(rng.normal(size=(ids.size, LANES)).astype(np.float32) * 1e-3)
            perm = jnp.asarray(np.argsort(lists[name], kind="stable").astype(np.int32))
            checksum(f"xla.gather_sorted_rows.{name}",
                     lambda i, p, r: (r[(p + i) % p.shape[0]],), perm, rows,
                     entries=int(ids.size))


    # 4. the kernel.
    def section_4():
        chosen = row_update.CHUNK, row_update.RING
        for chunk, ring in ((8, 4), (16, 4), (32, 2), (32, 4)):
            row_update.CHUNK, row_update.RING = chunk, ring
            for name in ("centres", "u"):
                ids = lists[name]
                rows = jnp.asarray(rng.normal(size=(ids.size, LANES)).astype(np.float32) * 1e-3)
                timed(f"kernel.add_rows_sorted.{name}.chunks_of_{chunk}_ring_{ring}",
                      lambda t, i, r: row_update.add_rows_sorted(t, i, r),
                      jnp.asarray(np.sort(ids)), rows)
        row_update.CHUNK, row_update.RING = chosen
        for tile in (1024, 4096):
            ids = lists["u"]
            rows = jnp.asarray(rng.normal(size=(ids.size, LANES)).astype(np.float32) * 1e-3)
            timed(f"kernel.add_rows_sorted.u.tile_{tile}",
                  lambda t, i, r: row_update.add_rows_sorted(t, i, r, tile=tile),
                  jnp.asarray(np.sort(ids)), rows)
        # one entry a group: the walk's cost a group without the runs
        ids = np.unique(lists["u"] // 8).astype(np.int32) * 8
        rows = jnp.asarray(rng.normal(size=(ids.size, LANES)).astype(np.float32) * 1e-3)
        timed("kernel.add_rows_sorted.one_entry_a_group",
              lambda t, i, r: row_update.add_rows_sorted(t, i, r),
              jnp.asarray(ids), rows, entries=int(ids.size))
        # one group named by every entry: the walk's cost an entry
        timed("kernel.add_rows_sorted.one_group",
              lambda t, i, r: row_update.add_rows_sorted(t, i, r),
              jnp.asarray(np.sort(lists["u"] % 8)), rows[:1].repeat(lists["u"].size, 0),
              entries=int(lists["u"].size))

        # The kernel's bits on the chip: the scatter-add on the sorted list.
        ids = jnp.asarray(np.sort(lists["u"]))
        rows = jnp.asarray(rng.normal(size=(ids.shape[0], LANES)).astype(np.float32))
        start = jnp.asarray(rng.normal(size=(ROWS, LANES)).astype(np.float32))
        want = start.at[ids].add(rows)
        got = jax.jit(row_update.add_rows_sorted, donate_argnums=0)(start, ids, rows)
        out["kernel_floats_off_xla_scatter_add"] = int(jnp.sum(got != want))
        out["kernel_widest_gap"] = float(jnp.max(jnp.abs(got - want)))

    for n, section in enumerate((section_1, section_2, section_3, section_4), 1):
        if str(n) in sections:
            section()
    out["readings"] = readings
    print(json.dumps({k: v for k, v in out.items() if k != "readings"}), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/row_update_probe.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
