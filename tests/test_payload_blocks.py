"""The blocked payload walk in fast memory (PR 53,
``kernels/payload_blocks.py``): a TPU's Mosaic kernels, here interpreted,
held to the gather and the scatter-add that ``ops.sparse.block_lookup`` /
``block_accumulate`` (their payload forms) are held to in
``tests/test_fm_sparse.py``:

- the lookup is ``table[:, idx]`` bit for bit, at the factorization
  machine's 17 floats a column and at payloads whose rows lie otherwise
  in a chunk (5: none along the sublanes; 36: two tiles of them and four
  after), over a block of 128 columns, a short one of 256, one chunk,
  several chunks, a block that ends inside a chunk; first and last
  column; a padded zero-value cell out of range;
- the accumulation is the float64 scatter-add of the cells' gradients
  within float32's sum bound, and repeats to the bit; two slots on
  overlapping blocks give each its own sums (the trainer adds them);
- the rows' sums over the slots, and their factors' squares, that the
  lookup makes beside ``xp``, against float64 sums of ``xp``;
- every product's operands are exact: int8 digits of the floats' bits
  for the long lookup, bfloat16 parts that accumulate in float32
  everywhere else;
- where the kernels apply is read off the step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flinkml_tpu.kernels import _split, payload_blocks

#: Block lengths: 128 columns and the other short rung; one chunk of 16
#: rows exactly, less than one, two, three, and 104 rows (``fm-criteo``'s
#: 13,312 columns: six chunks and a half).
LENGTHS = [128, 256, 2048, 1024, 4096, 6144, 13312]
PAYLOADS = [17, 5, 36]
BATCH, DIM_ROWS = 256, 120


def _step(payload, lengths, seed=0, batch=BATCH):
    """A step's operands: ``(table, cells, vals, starts, where, local)``.
    The walked slots lie among the cells' rows in another order, rows 2
    and 5 are no walked slot's (never read); the first slot's first
    cells name its block's first and last column."""
    rng = np.random.default_rng(seed)
    width = len(lengths) + 2
    where = [row for row in rng.permutation(width) if row not in (2, 5)]
    where = where[:len(lengths)]
    table = rng.standard_normal((payload, DIM_ROWS, 128)).astype(np.float32)
    table[:, :, :4] = [0.0, 1e-30, -3.5e20, 1 + 2.0 ** -23]
    starts = np.zeros(width, np.int32)
    local = np.full((width, batch), 1 << 30, np.int32)
    for row, length in zip(where, lengths):
        starts[row] = rng.integers(0, DIM_ROWS - length // 128 + 1)
        local[row] = rng.integers(0, length, batch)
        local[row, :4] = [0, length - 1, 1, 2]
    cells = local + 128 * starts[:, None]
    vals = rng.standard_normal((width, batch)).astype(np.float32)
    return table, cells, vals, starts, where, local


def _lookup(lengths, where, table, cells, vals, starts):
    xps, sums, squares = payload_blocks.lookup(
        lengths, where, jnp.asarray(table), jnp.asarray(cells),
        jnp.asarray(vals), jnp.asarray(starts), interpret=True)
    xp = np.concatenate([np.asarray(xp) for xp in xps])
    # The rows' sums over the slots, made beside the lookup: float32 sums
    # of the same floats in another order.
    np.testing.assert_allclose(
        np.asarray(sums), xp.astype(np.float64).sum(axis=0), rtol=0,
        atol=2.0 ** -22 * np.abs(xp).sum(axis=0).max())
    with np.errstate(over="ignore", under="ignore"):    # float32's squares
        wanted = np.square(xp[:, 1:]).astype(np.float64).sum(axis=(0, 1))
    small = wanted < 1e30           # the rest overflows in float32, as it may
    np.testing.assert_allclose(np.asarray(squares)[small], wanted[small],
                               rtol=2.0 ** -20, atol=1e-30)
    return xps, xp


@pytest.mark.parametrize("payload", PAYLOADS)
@pytest.mark.parametrize("length", LENGTHS)
def test_the_payload_lookup_is_the_gather_bit_for_bit(length, payload):
    table, cells, vals, starts, where, _ = _step(payload, [length] * 2, length)
    vals[:] = 1.0           # the looked-up floats themselves
    _, got = _lookup([length] * 2, where, table, cells, vals, starts)
    flat = table.reshape(payload, -1)
    want = np.stack([flat[:, cells[row]] for row in where])
    assert got.dtype == np.float32 and got.shape == (2, payload, BATCH)
    assert got.tobytes() == want.tobytes()


def test_a_bfloat16_table_fails_the_bit_for_bit_test():
    """The control: the same lookup in a table rounded to bfloat16, which
    is what one pass of the MXU makes of it."""
    table, cells, vals, starts, where, _ = _step(17, [256, 6144], 3)
    vals[:] = 1.0
    low = np.asarray(jnp.asarray(table).astype(jnp.bfloat16).astype(jnp.float32))
    _, got = _lookup([256, 6144], where, low, cells, vals, starts)
    flat = table.reshape(17, -1)
    want = np.stack([flat[:, cells[row]] for row in where])
    assert np.mean(got != want) > 0.9
    np.testing.assert_allclose(got, want, rtol=2.0 ** -8, atol=1e-30)


@pytest.mark.parametrize("payload", PAYLOADS)
def test_a_step_of_every_kind_of_slot_is_the_gather_and_the_scatter_add(payload):
    """Short and long slots in one walk (a kernel each), the cells'
    values as they come: ``xp`` to the bit, the gradient's sums against a
    float64 scatter-add within float32's sum bound, twice to the bit."""
    lengths = [128, 128, 256, 1024, 6144, 6144, 13312]
    table, cells, vals, starts, where, local = _step(payload, lengths, payload)
    xps, xp = _lookup(lengths, where, table, cells, vals, starts)
    flat = table.reshape(payload, -1)
    for got, row in zip(xp, where):
        assert got.tobytes() == (vals[row][None] * flat[:, cells[row]]).tobytes()
    rng = np.random.default_rng(9)
    mult = rng.standard_normal(BATCH).astype(np.float32)
    base = rng.standard_normal((payload, BATCH)).astype(np.float32)

    def sums():
        return [np.asarray(s) for s in payload_blocks.accumulate(
            lengths, where, jnp.asarray(cells), jnp.asarray(vals),
            jnp.asarray(starts), jnp.asarray(mult), jnp.asarray(base), xps,
            interpret=True)]

    first = sums()
    factors = (np.arange(payload) > 0)[:, None]
    for got, mine, row, length in zip(first, xp, where, lengths):
        assert got.shape == (payload, length // 128, 128) and got.dtype == np.float32
        grads = ((mult * vals[row])[None] * (base - np.where(factors, mine, 0))
                 ).astype(np.float32)
        exact = np.zeros((payload, length))
        size = np.zeros((payload, length))
        for p in range(payload):
            np.add.at(exact[p], local[row], grads[p].astype(np.float64))
            np.add.at(size[p], local[row], np.abs(grads[p]).astype(np.float64))
        # a float32 sum of n terms: n ulps of the terms' sizes at the most
        bound = 2.0 ** -23 * BATCH * np.maximum(size, 2.0 ** -100)
        assert (np.abs(got.reshape(payload, length) - exact) <= bound).all()
    for a, b in zip(first, sums()):
        assert a.tobytes() == b.tobytes()


def test_many_short_slots_meet_a_smaller_tile_of_the_batch(monkeypatch):
    """The short kernels hold a tile of every short slot at once, so
    their tile shrinks as the slots grow in number (here: the room does)
    while the long kernels keep theirs: the same looked-up floats, the
    same sums but for the order the tiles come in."""
    lengths = [128] * 3 + [256] * 2 + [2048]
    table, cells, vals, starts, where, _ = _step(17, lengths, 8)
    flat = table.reshape(17, -1)
    rng = np.random.default_rng(10)
    mult = jnp.asarray(rng.standard_normal(BATCH).astype(np.float32))
    base = jnp.asarray(rng.standard_normal((17, BATCH)).astype(np.float32))

    def both():
        xps, xp = _lookup(lengths, where, table, cells, vals, starts)
        for got, row in zip(xp, where):
            assert got.tobytes() == (vals[row][None] * flat[:, cells[row]]).tobytes()
        return [np.asarray(s) for s in payload_blocks.accumulate(
            lengths, where, jnp.asarray(cells), jnp.asarray(vals),
            jnp.asarray(starts), mult, base, xps, interpret=True)]

    assert payload_blocks.short_tile_rows(BATCH, 17, 5, 256, 8) == BATCH
    whole = both()
    monkeypatch.setattr(payload_blocks, "_SHORT_BYTES", 9 * 256 * 1024)
    assert payload_blocks.short_tile_rows(BATCH, 17, 5, 256, 8) == 128
    assert payload_blocks.tile_rows(BATCH, 17) == BATCH
    halved = both()
    for a, b in zip(whole[:5], halved[:5]):
        np.testing.assert_allclose(a, b, rtol=0, atol=2.0 ** -20 * np.abs(a).max())
    assert whole[5].tobytes() == halved[5].tobytes()      # the long slot's


def test_cells_out_of_their_block_with_value_zero_change_nothing():
    """The zero rows a shard is padded with: index 0 in every slot, which
    lies before a block that starts further on."""
    lengths = [256, 4096]
    table, cells, vals, starts, where, local = _step(17, [256, 4096], 5)
    starts[where] += 3
    cells = local + 128 * starts[:, None]
    inside = cells.copy()
    cells[:, 10:20] = 0             # column 0: before every block
    vals[:, 10:20] = 0.0
    xps, xp = _lookup(lengths, where, table, cells, vals, starts)
    assert np.isfinite(xp).all() and not xp[:, :, 10:20].any()
    ones = jnp.ones(BATCH, jnp.float32)
    base = jnp.ones((17, BATCH), jnp.float32)
    args = (jnp.asarray(vals), jnp.asarray(starts), ones, base)
    out = payload_blocks.accumulate(
        lengths, where, jnp.asarray(cells), *args, xps, interpret=True)
    same = payload_blocks.accumulate(
        lengths, where, jnp.asarray(inside), *args,
        _lookup(lengths, where, table, inside, vals, starts)[0], interpret=True)
    for a, b in zip(out, same):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_two_slots_on_overlapping_blocks_have_sums_of_their_own():
    """Blocks may overlap: each slot's sums are its own cells', and the
    trainer adds them one after another."""
    lengths = [256, 256]
    table, cells, vals, starts, where, local = _step(17, [256, 256], 6)
    starts[where] = [4, 5]          # one row of 128 columns shared
    cells = local + 128 * starts[:, None]
    xps, xp = _lookup(lengths, where, table, cells, vals, starts)
    out = payload_blocks.accumulate(
        lengths, where, jnp.asarray(cells), jnp.asarray(vals), jnp.asarray(starts),
        jnp.ones(BATCH, jnp.float32), jnp.ones((17, BATCH), jnp.float32), xps,
        interpret=True)
    for got, row in zip(out, where):
        exact = np.zeros(256)
        np.add.at(exact, local[row], vals[row].astype(np.float64))
        np.testing.assert_allclose(
            np.asarray(got)[0].reshape(-1), exact, rtol=0, atol=2e-5)


def _flat(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _flat(sub)


def test_the_products_operands_are_exact_and_the_digits_are_the_floats_bits():
    """What stands in for ``HIGHEST``: the long lookup's operands are
    int8 (a float's 32 bits in four digits that put its bits together
    again, whatever they are) and accumulate in int32; the short lookup's
    and both accumulations' are bfloat16 (one pass each on the MXU) and
    accumulate in float32; four kernels a step, two a direction."""
    lengths = [256, 6144]
    table, cells, vals, starts, where, _ = _step(17, lengths, 7)
    odd = np.asarray([-0.0, 1e-42, np.inf, -np.inf, np.nan, 3.4e38], np.float32)
    table[3, 40, :6] = odd
    four = [np.asarray(d).astype(np.int64) for d in _split.digits(
        jnp.asarray(table))]
    assert all(d.min() >= -128 and d.max() < 128 for d in four)
    bits = (four[0] + (four[1] << 8) + (four[2] << 16) + (four[3] << 24)
            ).astype(np.int32)
    assert bits.tobytes() == table.tobytes()
    operand = np.asarray(payload_blocks.block_digits(
        jnp.asarray(table), [6144], [jnp.asarray(starts)[where[1]]]))
    assert operand.shape == (1, 3, 4, 16 * 17, 128) and operand.dtype == np.int8
    block = table[:, starts[where[1]]:starts[where[1]] + 48]
    for mine, digit in zip(operand.transpose(2, 0, 1, 3, 4), four):
        assert np.array_equal(
            np.asarray(payload_blocks._unchunked(jnp.asarray(mine), 17))[0],
            digit[:, starts[where[1]]:starts[where[1]] + 48])
    del block
    args = (jnp.asarray(cells), jnp.asarray(vals), jnp.asarray(starts))

    def step(table, cells, vals, starts):
        xps, _, _ = payload_blocks.lookup(lengths, where, table, cells, vals,
                                          starts, interpret=True)
        return payload_blocks.accumulate(
            lengths, where, cells, vals, starts, vals[0], xps[0][0], xps,
            interpret=True)

    program = jax.make_jaxpr(step)(jnp.asarray(table), *args)
    calls = [eqn for eqn in _flat(program.jaxpr)
             if eqn.primitive.name == "pallas_call"]
    assert len(calls) == 4
    operands = []
    for call in calls:
        (dot,) = [eqn for eqn in _flat(call.params["jaxpr"])
                  if eqn.primitive.name == "dot_general"]
        kinds = {str(v.aval.dtype) for v in dot.invars}
        assert len(kinds) == 1
        operands.append((kinds.pop(), str(dot.params["preferred_element_type"])))
    assert sorted(operands) == [("bfloat16", "float32")] * 3 + [("int8", "int32")]


def test_where_the_kernels_apply_is_read_off_the_step():
    criteo = ([128] * 11 + [256] * 10 + [512, 1024, 1024, 2048, 3072, 4096, 6144,
                                         6144, 13312, 15360] + [26624] * 8)
    reason = payload_blocks.unsupported_reason
    assert reason(jnp.float32, 65_536, criteo, 17) is None
    assert reason(jnp.float32, 16_384, criteo, 17) is None      # four chips
    assert payload_blocks.tile_rows(65_536, 17) == 4096
    assert payload_blocks.tile_rows(384, 17) == 128
    assert payload_blocks.tile_rows(65_536, 129) == 512     # a chunk's product
    assert "float64" in reason(jnp.float64, 65_536, criteo, 17)
    assert "whole tiles" in reason(jnp.float32, 100, criteo, 17)
    assert "no blocked slot" in reason(jnp.float32, 65_536, [], 17)
    # a block's parts stay while its tiles pass: 116,736 columns x 17 do
    assert reason(jnp.float32, 65_536, [116_736] * 39, 17) is None
    assert "fast memory" in reason(jnp.float32, 65_536, [194_560], 17)
    assert reason(jnp.float32, 65_536, [194_560], 9) is None
    # ... and a batch's sums over the slots: 65,536 rows x 17 do
    assert "rows' sums" in reason(jnp.float32, 131_072, criteo, 17)
    assert reason(jnp.float32, 131_072, criteo, 5) is None
    # ... and a tile of EVERY short slot at once: fm-criteo's 21 keep the
    # whole tile, more halve it, a hundred and thirty narrow fields fall
    # back (Mosaic refused 76 of them at a tile of 4,096, PR 53's review)
    short_tile = payload_blocks.short_tile_rows
    assert short_tile(65_536, 17, 21, 256, 39) == 4096
    assert short_tile(65_536, 17, 36, 256, 36) == 4096
    assert short_tile(65_536, 17, 37, 256, 37) == 2048
    assert short_tile(65_536, 17, 76, 256, 76) == 1024
    assert short_tile(65_536, 17, 129, 256, 129) == 128
    assert short_tile(65_536, 5, 76, 256, 76) == 2048
    assert short_tile(256, 17, 129, 256, 129) == 128
    assert reason(jnp.float32, 65_536, [256] * 129 + [26_624], 17) is None
    assert "a tile of them all" in reason(jnp.float32, 65_536, [256] * 130, 17)
    assert "a tile of them all" in reason(
        jnp.float32, 65_536, [128] * 21, 17, width=40_000)
    assert payload_blocks.planes(17) == (1, 16)
    assert payload_blocks.planes(5) == (5, 0)
    assert payload_blocks.planes(36) == (4, 32)
    assert [payload_blocks.chunks_of(n) for n in (128, 2048, 2176, 26624)] == [
        1, 1, 2, 13]
