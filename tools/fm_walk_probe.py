"""Device readings behind ``kernels/payload_blocks``: what one step of
``fm-criteo.fit`` pays to look its 39 blocked slots' rows up and to
accumulate their gradient, direction by direction and block length by
block length, at the cell's own shapes (a window of 65,536 rows x 39
cells, a table ``[17, 7813, 128]``, the plan ``tests/test_chip_compile``
pins: 269,696 block columns; the cells uniform in their blocks, the
blocks side by side).

Prints one JSON line a reading, ms a call (the host's clock around
``REPS`` calls of one jitted function, the last waited for; the least of
three):

1. ``whole``: the two kernels over the whole plan, and XLA's walk
   (``_fm_sparse.xla_lookup`` / ``xla_accumulate``, what every backend
   but a TPU runs) over the same operands, and XLA's accumulation fed
   from the KERNEL's looked-up rows (the step the lookup kernels alone
   would make); the looked-up rows of the two compared to the bit and
   the sums to float32's rounding; the MXU's own time for the products
   at the chip's peak beside them (three bfloat16 passes; the long
   lookup's four int8 passes at twice the rate);
2. ``lengths``: each block length's slots alone, both directions, kernel
   and XLA (ms a slot);
3. ``forms``: one long slot through the kernels as they ship (``lo``
   contracted: the one-hot of the lane is the MXU's weights, a chunk's
   rows stream) and, in three bfloat16 parts, with ``hi`` contracted (the block's rows, three
   parts along the contraction, are the weights; the one-hot of the row
   streams and the lane is picked among 128): the lookup alone, a form
   the kernels do not ship;
4. ``trace``: seconds to trace and lower the two kernels' call sites in
   this process (what every process pays whatever the compile cache
   holds).

Run it through the chip tool: ``python tools/fm_walk_probe.py [seed
[sections [lengths]]]`` (``sections`` a string of the numbers above, all
four by default, about four minutes; ``lengths`` the block lengths
section 2 reads, with commas between, every length of the plan by
default). It refuses to start off a TPU, as its siblings do: an
interpreted kernel's time on a CPU is no reading, and the values are
``tests/test_payload_blocks.py``'s to hold.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PLAN = (
    128, 128, 256, 256, 128, 256, 256, 128, 256, 256, 128, 256, 256, 2048, 1024,
    26624, 26624, 512, 128, 13312, 1024, 128, 26624, 6144, 26624, 4096, 128,
    15360, 26624, 128, 6144, 3072, 128, 26624, 256, 128, 26624, 256, 26624)
PAYLOAD, DIM_ROWS, BATCH, REPS = 17, 7813, 65_536, 5
#: bfloat16 multiply-adds a second of one v5e chip (197 TFLOP/s; int8's
#: are twice as many, 393 TOP/s).
PEAK_MACS = 98.5e12


def say(**reading):
    print(json.dumps(reading), flush=True)


def operands(seed: int, plan=PLAN):
    """``(table, ib, vb, starts, mult)`` as the step holds them: the
    window's cells ``[batch, width]`` (rows along the lanes on the chip),
    each slot's uniform in a block of its own, the blocks side by side."""
    rng, batch = np.random.default_rng(seed), BATCH
    starts = np.concatenate([[0], np.cumsum(plan)[:-1]]) // 128
    idx = np.stack([128 * at + rng.integers(0, length, batch)
                    for at, length in zip(starts, plan)], axis=1)
    return (rng.standard_normal((PAYLOAD, DIM_ROWS, 128)).astype(np.float32),
            idx.astype(np.int32),
            np.full(idx.shape, 1 / np.sqrt(len(plan)), np.float32),
            starts.astype(np.int32),
            rng.standard_normal(batch).astype(np.float32))


def timed(fn, *args):
    """ms a call: the least of three rounds of ``REPS`` calls."""
    import jax

    jax.block_until_ready(fn(*args))
    best = np.inf
    for _ in range(3):
        start = time.perf_counter()
        for _ in range(REPS):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - start) / REPS)
    return 1e3 * best


def directions(plan):
    """``(kernel_lookup, kernel_accumulate, xla_lookup, xla_accumulate,
    mixed_accumulate)`` jitted for ``plan``: a lookup gives ``(xp, sums
    [17, batch])``, an accumulation the gradient ``[17, 7813, 128]``;
    ``mixed_accumulate`` is XLA's accumulation fed from the KERNEL's
    ``xp [slots, 17, batch]``, a group's rows a slice of it read ``[slots,
    batch, 17]``: the step with the lookup kernels alone, which is why
    the accumulation kernels are there (PERF.md section 6, PR 53)."""
    import jax
    import jax.numpy as jnp

    from flinkml_tpu.kernels import payload_blocks
    from flinkml_tpu.models import _fm_sparse

    walk = _fm_sparse._walk(plan)
    precision = _fm_sparse.LOOKUP_PRECISION
    factors = jnp.arange(PAYLOAD) > 0
    groups = _fm_sparse.sparse.block_groups(plan, BATCH, PAYLOAD)

    @jax.jit
    def kernel_lookup(table, ib, vb, starts):
        xps, sums, _ = payload_blocks.lookup(*walk, table, ib.T, vb.T, starts)
        return xps, sums

    @jax.jit
    def kernel_accumulate(table, ib, vb, starts, mult, xp, sums):
        grad = jnp.zeros_like(table)
        for j, slot_grad in zip(walk[1], payload_blocks.accumulate(
                *walk, ib.T, vb.T, starts, mult, sums, xp)):
            grad = _fm_sparse._add_rows(grad, slot_grad, starts[j])
        return grad

    @jax.jit
    def xla_lookup(table, ib, vb, starts):
        sums, _, walked = _fm_sparse.xla_lookup(
            table, ib, vb, starts, plan, precision)
        return [xp for *_, xp in walked], sums

    @jax.jit
    def xla_accumulate(table, ib, vb, starts, mult, xps, sums):
        walked = []
        for (length, slots), xp in zip(groups, xps):
            first = [starts[j] for j in slots]
            walked.append((
                length, first,
                _fm_sparse._slot_major(ib, slots) - 128 * jnp.stack(first)[:, None],
                _fm_sparse._slot_major(vb, slots), xp))

        def cell_grads(xs, xp):
            return (mult * xs)[..., None] * (sums.T - jnp.where(factors, xp, 0))

        return _fm_sparse.xla_accumulate(
            jnp.zeros_like(table), walked, cell_grads, precision)

    @jax.jit
    def mixed_accumulate(table, ib, vb, starts, mult, xp, sums):
        # The groups follow the walk, the short slots first: slices.
        short = len(payload_blocks._kinds(walk[0])[0])
        xps, at = [], 0
        for _, slots in groups:
            rows = (xp[0][at:at + len(slots)] if at < short
                    else xp[-1][at - short:at - short + len(slots)])
            xps.append(jnp.swapaxes(rows, 1, 2))
            at += len(slots)
        return xla_accumulate(table, ib, vb, starts, mult, xps, sums)

    return (kernel_lookup, kernel_accumulate, xla_lookup, xla_accumulate,
            mixed_accumulate)


def read(plan, args, compare: bool):
    """Both directions of ``plan``, kernel and XLA, ms a call."""
    import jax

    (kernel_lookup, kernel_accumulate, xla_lookup, xla_accumulate,
     mixed_accumulate) = directions(plan)
    table, ib, vb, starts, mult = args
    out = {}
    xp, sums = kernel_lookup(table, ib, vb, starts)
    out["kernel_lookup_ms"] = timed(kernel_lookup, table, ib, vb, starts)
    out["kernel_accumulate_ms"] = timed(
        kernel_accumulate, table, ib, vb, starts, mult, xp, sums)
    out["xla_accumulate_of_the_kernels_rows_ms"] = timed(
        mixed_accumulate, table, ib, vb, starts, mult, xp, sums)
    xps, xsums = xla_lookup(table, ib, vb, starts)
    out["xla_lookup_ms"] = timed(xla_lookup, table, ib, vb, starts)
    out["xla_accumulate_ms"] = timed(
        xla_accumulate, table, ib, vb, starts, mult, xps, xsums)
    if compare:
        # XLA's rows are slot major [slots, rows, 17] a group, the groups
        # in the walk's order.
        theirs = np.concatenate([np.asarray(x) for x in xps]).transpose(0, 2, 1)
        out["looked_up_floats_off"] = int(np.sum(
            np.concatenate([np.asarray(x) for x in xp]).view(np.int32)
            != theirs.view(np.int32)))
        mine = np.asarray(kernel_accumulate(table, ib, vb, starts, mult, xp, sums))
        ours = np.asarray(xla_accumulate(table, ib, vb, starts, mult, xps, xsums))
        both = np.asarray(mixed_accumulate(table, ib, vb, starts, mult, xp, sums))
        out["sums_widest_gap"] = float(np.abs(mine - ours).max())
        out["sums_widest_gap_of_the_kernels_rows"] = float(np.abs(both - ours).max())
        out["sums_widest"] = float(np.abs(ours).max())
        del theirs, mine, ours, both
    del xp, sums, xps, xsums
    jax.clear_caches()
    return out


def hi_contracted_lookup(length: int, tile: int = 2048):
    """A lookup of one slot's rows with ``hi`` contracted (module
    docstring, 3): ``(table, cells [1, batch], vals) -> [17, batch]``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from flinkml_tpu.kernels._split import rounded_parts
    from flinkml_tpu.kernels.sparse_blocks import _as_operand, _one_hot

    rows = -(-length // 128 // 16) * 16
    depth = -(-3 * rows // 128) * 128

    def body(cells_ref, vals_ref, parts_ref, out_ref):
        local = cells_ref[...]
        hi, lo = local >> 7, local & 127
        rows_of = _as_operand(_one_hot(hi, rows))
        rest = depth - 3 * rows
        stacked = jnp.concatenate([rows_of] * 3 + (
            [jnp.zeros((rest, tile), jnp.bfloat16)] if rest else []), axis=0)
        lanes_of = _one_hot(lo, 128)

        def one_float(p, carry):
            looked = jnp.dot(parts_ref[p], stacked,
                             preferred_element_type=jnp.float32)
            out_ref[pl.ds(p, 1), :] = vals_ref[...] * jnp.sum(
                jnp.where(lanes_of, looked, 0.0), axis=0, keepdims=True)
            return carry

        jax.lax.fori_loop(0, PAYLOAD, one_float, 0)

    @jax.jit
    def lookup(table, cells, vals):
        # [17, 128 (lo), 3 rows (part, hi)]: the block's rows turned.
        block = jnp.pad(table[:, :length // 128],
                        ((0, 0), (0, rows - length // 128), (0, 0)))
        parts = jnp.concatenate(
            [p.transpose(0, 2, 1)
             for p in rounded_parts(block, in_kernel=False)], axis=2)
        parts = jnp.pad(parts, ((0, 0), (0, 0), (0, depth - 3 * rows)))
        batch = cells.shape[1]
        return pl.pallas_call(
            body, grid=(batch // tile,),
            in_specs=[pl.BlockSpec((1, tile), lambda t: (0, t))] * 2
            + [pl.BlockSpec(parts.shape, lambda t: (0, 0, 0))],
            out_specs=pl.BlockSpec((PAYLOAD, tile), lambda t: (0, t)),
            out_shape=jax.ShapeDtypeStruct((PAYLOAD, batch), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=64 * 1024 * 1024),
        )(cells, vals, parts)

    return lookup


def main(seed: int = 0, sections: str = "1234", lengths: str = "") -> None:
    import jax
    import jax.numpy as jnp

    from flinkml_tpu.kernels import payload_blocks

    if jax.default_backend() != "tpu":
        raise SystemExit(f"backend {jax.default_backend()}: the readings are a chip's")
    say(device=jax.devices()[0].device_kind, seed=seed, batch=BATCH,
        payload=PAYLOAD, columns=sum(PLAN))
    if "4" in sections:
        args = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in operands(seed)]
        kernel_lookup, kernel_accumulate, *_ = directions(PLAN)
        start = time.perf_counter()
        kernel_lookup.trace(*args[:4]).lower()
        first = time.perf_counter() - start
        xp = [jax.ShapeDtypeStruct((len(kind), PAYLOAD, BATCH), jnp.float32)
              for kind in payload_blocks._kinds(sorted(PLAN)) if kind]
        sums = jax.ShapeDtypeStruct((PAYLOAD, BATCH), jnp.float32)
        start = time.perf_counter()
        kernel_accumulate.trace(*args, xp, sums).lower()
        say(reading="trace", lookup_s=first,
            accumulate_s=time.perf_counter() - start)
        jax.clear_caches()
    placed = [jax.device_put(a) for a in operands(seed)]
    if "1" in sections:
        # The long blocks' chunks at the MXU's peak: the accumulation's
        # three bfloat16 passes, the lookup's four int8 passes at twice
        # the rate; and three bfloat16 passes over the blocks' own columns.
        chunked = PAYLOAD * BATCH * 2048 * sum(
            payload_blocks.chunks_of(length) for length in PLAN
            if length > payload_blocks.SHORT)
        say(reading="whole",
            mxu_ms_three_passes=1e3 * 3 * PAYLOAD * sum(PLAN) * BATCH / PEAK_MACS,
            chunked_mxu_ms_accumulate=1e3 * 3 * chunked / PEAK_MACS,
            chunked_mxu_ms_lookup=1e3 * 4 * chunked / (2 * PEAK_MACS),
            **read(PLAN, placed, compare=True))
    if "2" in sections:
        table, ib, vb, starts, mult = placed
        for length in [int(n) for n in lengths.split(",") if n] or sorted(set(PLAN)):
            slots = [j for j, other in enumerate(PLAN) if other == length]
            keep = jnp.asarray(slots)
            got = read(tuple(PLAN[j] for j in slots),
                       (table, ib[:, keep], vb[:, keep], starts[keep], mult),
                       compare=False)
            say(reading="length", length=length, slots=len(slots),
                mxu_ms_three_passes_a_slot=1e3 * 3 * PAYLOAD * length * BATCH / PEAK_MACS,
                **{name.replace("_ms", "_ms_a_slot"): ms / len(slots)
                   for name, ms in got.items()})
    if "3" in sections:
        table, ib, vb, starts, _ = placed
        for length in (26624, 6144):
            j = PLAN.index(length)
            cells = (ib[:, j] - 128 * starts[j])[None]
            block = jax.lax.dynamic_slice_in_dim(
                table, starts[j], length // 128, axis=1)
            lo, *_ = directions((length,))
            shipped = lo(block, cells.T, vb[:, j:j + 1], jnp.zeros(1, jnp.int32))
            for tile in (1024, 2048):
                hi = hi_contracted_lookup(length, tile)
                turned = hi(block, cells, vb[:, j][None])
                say(reading="form", length=length, tile=tile,
                    hi_contracted_ms=timed(hi, block, cells, vb[:, j][None]),
                    lo_contracted_ms=timed(
                        lo, block, cells.T, vb[:, j:j + 1], jnp.zeros(1, jnp.int32)),
                    floats_off=int(np.sum(
                        np.asarray(shipped[0][0][0]).view(np.int32)
                        != np.asarray(turned).view(np.int32))))


if __name__ == "__main__":
    main(*(kind(arg) for kind, arg in zip((int, str, str), sys.argv[1:4])))
