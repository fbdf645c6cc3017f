"""The blocked sparse step (PR 29): ELL slots whose columns sit in a block
of their own are looked up and accumulated by two-level one-hot products
(``ops.sparse.block_lookup``, ``block_accumulate``) under the plan
``ops.sparse.slot_block_plan`` reads off the cells.

- the lookup is the gather bit for bit, and the same lookup in a table
  rounded to bfloat16 is not: the test can tell float32 from the next
  precision down;
- the accumulate is ``zeros.at[idx].add(c)`` to float32 rounding, and
  repeats to the bit;
- the step under a mixed plan (blocked and general slots, two blocks
  overlapping) agrees with the step under no plan, three losses, on the
  eight-device mesh;
- the planner: one plan for two seeds of a field-blocked table and for
  two tables of one schema whose columns start elsewhere (the starts
  are an operand), none for rows hashed over all of ``dim``;
- a field-blocked table with cells missing (ragged rows) is laid one
  field a slot and planned the same way; hashed or text-like ragged
  rows keep their padded buckets;
- the sparse trainer under the empty plan lowers to the text of the
  step as it was before plans existed (a copy of it, kept here);
- the same lookup, accumulation and step through
  ``kernels.sparse_blocks`` (PR 39: a TPU's two Mosaic kernels, here
  interpreted, the cases marked ``kernel``), held to the same gather,
  scatter-add and general step.
"""

from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from flinkml_tpu.kernels import sparse_blocks
from flinkml_tpu.models import _linear_sgd
from flinkml_tpu.ops import sparse
from flinkml_tpu.utils.metrics import metrics

#: Block lengths either side of 128 rows of 128 lanes (below it the lane
#: index is contracted on the MXU, from it on the row index), and the
#: ladder's rungs the kernel treats differently: up to 4,096 columns one
#: stacked pass (8 to 128 columns a product row), 42 rows of 128 the
#: most three stacked parts of whole rows would be, 13,312 and 26,624
#: ``lr-criteo``'s long slots.
LENGTHS = [128, 256, 1024, 3072, 5376, 13312, 128 * 128, 128 * 136, 26624]
PATHS = ["xla", "kernel"]


def _cells(length, slots=3, rows=768, seed=0):
    rng = np.random.default_rng(seed)
    blocks = rng.standard_normal((slots, length)).astype(np.float32)
    blocks[0, :4] = [0.0, 1e-30, -3.5e20, np.float32(1) + np.float32(2) ** -23]
    local = rng.integers(0, length, (slots, rows)).astype(np.int32)
    local[0, :4] = [0, 1, 2, 3]
    return blocks, local, rng.standard_normal((slots, rows)).astype(np.float32)


def _lookup(path, blocks, local):
    """``blocks[s, local[s, b]]`` by XLA's products, or by the kernel
    (interpreted): a slot at a time with the value 1, so that its share
    of the margin IS the looked-up float."""
    if path == "xla":
        return np.asarray(jax.jit(sparse.block_lookup)(blocks, local))
    length = blocks.shape[1]
    one = jnp.ones((1, local.shape[1]), jnp.float32)
    none = jnp.zeros(1, jnp.int32)
    return np.stack([np.asarray(sparse_blocks.lookup_dot(
        [(length, 1)], [0], [jnp.asarray(blocks[s:s + 1]).reshape(1, -1, 128)],
        jnp.asarray(local[s:s + 1]), one, none, interpret=True))
        for s in range(blocks.shape[0])])


def _accumulate(path, local, contrib, length):
    """``zeros([S, length]).at[s, local[s, b]].add(contrib[s, b])``; the
    kernel takes the contributions as values times a multiplier a row."""
    if path == "xla":
        return np.asarray(jax.jit(sparse.block_accumulate, static_argnums=2)(
            local, contrib, length))
    mult = np.random.default_rng(9).uniform(0.5, 2.0, local.shape[1]).astype(
        np.float32)
    slots = local.shape[0]
    (sums,) = sparse_blocks.accumulate(
        [(length, slots)], range(slots), jnp.asarray(local),
        jnp.asarray(contrib / mult), jnp.zeros(slots, jnp.int32),
        jnp.asarray(mult), interpret=True)
    return np.asarray(sums).reshape(local.shape[0], length)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("length", LENGTHS)
def test_block_lookup_is_the_gather_bit_for_bit(length, path):
    blocks, local, _ = _cells(length)
    got = _lookup(path, blocks, local)
    want = np.take_along_axis(blocks, local, axis=1)
    assert got.dtype == np.float32
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("length", [256, 128 * 136])
def test_a_bfloat16_lookup_fails_the_bit_for_bit_test(length, path):
    """The control: the same lookup in a table rounded to bfloat16, which
    is what any precision under ``HIGHEST`` makes of it on the MXU."""
    blocks, local, _ = _cells(length)
    low = np.asarray(jnp.asarray(blocks).astype(jnp.bfloat16).astype(jnp.float32))
    got = _lookup(path, low, local)
    want = np.take_along_axis(blocks, local, axis=1)
    assert np.mean(got != want) > 0.9
    np.testing.assert_allclose(got, want, rtol=2.0 ** -8)


def test_the_products_ask_for_the_highest_precision():
    """On the CPU every precision is float32, so the bits cannot tell:
    the request itself is checked."""
    blocks, local, contrib = _cells(1024)
    for text in (
            jax.jit(sparse.block_lookup).lower(blocks, local).as_text(),
            jax.jit(sparse.block_accumulate, static_argnums=2).lower(
                local, contrib, 1024).as_text()):
        assert text.count("dot_general") == 1
        assert "precision = [HIGHEST, HIGHEST]" in text


@pytest.mark.parametrize("length", [256, 4096, 13312, 26624])
def test_the_kernels_products_are_bfloat16_parts_that_sum_to_the_float(length):
    """What stands in for ``HIGHEST`` in the kernels. A narrow block's
    lookup and every accumulation: every operand of every product is
    bfloat16 (one pass each on the MXU), the blocks' three parts sum back
    to the float32 bit for bit in either order, and the products
    accumulate in float32. A wide block's lookup (PR 58; 13,312 columns
    in room for 128 rows, 26,624 in 208): a lookup selects, so the
    operand is the four int8 digits of the floats' BITS, the one product
    int8 by int8 into int32, and the digits put together are the blocks'
    bits whatever they are; its accumulation adds floats and keeps the
    parts."""
    blocks, local, vals = _cells(length)
    (group,) = sparse_blocks.walk([(length, 3)])
    r = length // group.c
    operand = sparse_blocks.block_parts(
        [jnp.asarray(blocks).reshape(3, -1, 128)], group)
    if group.narrow:
        assert operand.dtype == jnp.bfloat16
        operand = np.asarray(operand.astype(jnp.float32))
        parts = [operand[:, :, p * group.rows:p * group.rows + r]
                 for p in range(3)]
        assert not operand[:, :, 3 * group.rows:].any()
        want = blocks.reshape(3, r, group.c).transpose(0, 2, 1)
        # Summed as they lie along the contraction, from either end (the
        # first and the last alone, added first, need not be a float32).
        hi, mid, lo = parts
        assert ((hi + mid) + lo).tobytes() == want.tobytes()
        assert (hi + (mid + lo)).tobytes() == want.tobytes()
        lookup_operands = ["bfloat16", "bfloat16", jnp.float32]
    else:
        assert operand.dtype == jnp.int8
        assert operand.shape == (3, 4 * group.rows, 128)
        operand = np.asarray(operand).astype(np.int32)
        four = [operand[:, k * group.rows:k * group.rows + r] for k in range(4)]
        for k in range(4):      # the room up to whole tiles is zeros
            assert not operand[:, k * group.rows + r:(k + 1) * group.rows].any()
        bits = (four[0] + (four[1] << 8)) + ((four[2] << 16) + (four[3] << 24))
        assert bits.dtype == np.int32
        assert bits.tobytes() == blocks.reshape(3, r, 128).tobytes()
        lookup_operands = ["int8", "int8", jnp.int32]
    args = (jnp.asarray(local), jnp.asarray(vals), jnp.zeros(3, jnp.int32))
    programs = (
        (jax.make_jaxpr(lambda l, v, at: sparse_blocks.lookup_dot(
            [(length, 3)], range(3), [jnp.asarray(blocks).reshape(3, -1, 128)],
            l, v, at, interpret=True))(*args), lookup_operands),
        (jax.make_jaxpr(lambda l, v, at: sparse_blocks.accumulate(
            [(length, 3)], range(3), l, v, at, v[0], interpret=True))(*args),
         ["bfloat16", "bfloat16", jnp.float32]))
    for program, (*operands, into) in programs:
        (dot,) = [eqn for call in _pallas_calls(program.jaxpr)
                  for eqn in _flat(call.params["jaxpr"])
                  if eqn.primitive.name == "dot_general"]
        assert [str(v.aval.dtype) for v in dot.invars] == operands
        assert dot.params["preferred_element_type"] == into


def _flat(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _flat(sub)


def _pallas_calls(jaxpr):
    return [eqn for eqn in _flat(jaxpr) if eqn.primitive.name == "pallas_call"]


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("length", LENGTHS)
def test_block_accumulate_is_the_scatter_add_and_repeats(length, path):
    _, local, contrib = _cells(length, rows=5120)
    got = _accumulate(path, local, contrib, length)
    assert got.shape == (local.shape[0], length) and got.dtype == np.float32
    exact = np.zeros(got.shape)
    for s in range(local.shape[0]):
        np.add.at(exact[s], local[s], contrib[s].astype(np.float64))
    scatter = np.stack([
        np.asarray(jnp.zeros(length, jnp.float32).at[local[s]].add(contrib[s]))
        for s in range(local.shape[0])])
    # Float32 rounding: as far from float64 as the scatter-add is (the
    # kernel's contributions are a float32 product first: one more ulp).
    assert np.abs(got - exact).max() < 4 * max(
        np.abs(scatter - exact).max(), 2.0 ** -22)
    assert got.tobytes() == _accumulate(path, local, contrib, length).tobytes()


@pytest.mark.parametrize("path", PATHS)
def test_cells_outside_the_block_with_value_zero_change_nothing(path):
    """The zero rows a shard is padded with: index 0 in every slot."""
    blocks, local, contrib = _cells(1024, rows=700 if path == "xla" else 768)
    local[:, 10:20] = -7000
    contrib[:, 10:20] = 0.0
    assert np.isfinite(_lookup(path, blocks, local)).all()
    kept = np.ones(local.shape[1], bool)
    kept[10:20] = False
    if path == "kernel":      # whole tiles: the same cells inside the block
        kept, local_in = slice(None), local.copy()
        local_in[:, 10:20] = 5
    else:
        local_in = local
    np.testing.assert_array_equal(
        _accumulate(path, local, contrib, 1024),
        _accumulate(path, local_in[:, kept], contrib[:, kept], 1024))


@pytest.mark.parametrize("tile", [128, 256, 512])
def test_the_kernels_walk_groups_of_every_kind_whatever_the_tile(tile, monkeypatch):
    """Several lengths a call (a body each, the slots of one a loop), at
    each tile a batch can be cut in: the margin's share and the sums
    against NumPy float64."""
    groups = [(128, 3), (256, 2), (2048, 1), (6144, 2), (26624, 1)]
    batch = 3 * tile if tile < 512 else 1024
    monkeypatch.setattr(sparse_blocks, "TILE", 512)
    assert sparse_blocks.tile_rows(batch, sparse_blocks.walk(groups)) == tile
    rng = np.random.default_rng(tile)
    blocks = [rng.standard_normal((n, length // 128, 128)).astype(np.float32)
              for length, n in groups]
    # The walked slots' rows among the step's cells, not in their order,
    # rows 2 and 5 another kind's (never read); every block starts at a
    # row of its own.
    where = [7, 0, 9, 3, 10, 1, 4, 8, 6]
    starts = rng.integers(0, 50, 11).astype(np.int32)
    lengths = [length for length, n in groups for _ in range(n)]
    local = np.full((11, batch), 1 << 30, np.int32)
    for row, length in zip(where, lengths):
        local[row] = rng.integers(0, length, batch)
    cells = local + 128 * starts[:, None]
    vals = rng.standard_normal(local.shape).astype(np.float32)
    mult = rng.standard_normal(batch).astype(np.float32)
    flat = [b[i].reshape(-1) for b in blocks for i in range(b.shape[0])]
    want = sum(vals[row].astype(np.float64) * block[local[row]]
               for row, block in zip(where, flat))
    operands = (jnp.asarray(cells), jnp.asarray(vals), jnp.asarray(starts))
    got = np.asarray(sparse_blocks.lookup_dot(
        groups, where, [jnp.asarray(b) for b in blocks], *operands,
        interpret=True))
    np.testing.assert_allclose(got, want, rtol=0, atol=4e-6)
    sums = sparse_blocks.accumulate(
        groups, where, *operands, jnp.asarray(mult), interpret=True)
    rows = iter(where)
    for (length, n), out in zip(groups, sums):
        assert out.shape == (n, length // 128, 128)
        for i in range(n):
            row = next(rows)
            exact = np.zeros(length)
            np.add.at(exact, local[row], vals[row].astype(np.float64) * mult)
            np.testing.assert_allclose(
                np.asarray(out[i]).reshape(-1), exact, rtol=0, atol=2e-5)


def test_where_the_kernels_apply_is_read_off_the_step(monkeypatch):
    from flinkml_tpu.kernels import _mosaic

    criteo = [(128, 11), (256, 10), (4096, 1), (26624, 8)]
    long = [(194_560, 5), (59_392, 2), (8_192, 32)]     # Criteo field by field
    reason = sparse_blocks.unsupported_reason
    assert sparse_blocks.tile_rows(65_536, sparse_blocks.walk(criteo)) == sparse_blocks.TILE
    # a long block's product is cut in shorter tiles; a batch has to be tiles
    assert sparse_blocks.tile_rows(65_536, sparse_blocks.walk(long)) == 512
    assert sparse_blocks.tile_rows(384, sparse_blocks.walk(criteo)) == 128
    assert sparse_blocks.tile_rows(100, sparse_blocks.walk(criteo)) is None
    assert reason(jnp.float32, 65_536, criteo) is None
    assert reason(jnp.float32, 65_536, long) is None
    assert "bfloat16" in reason(jnp.bfloat16, 65_536, criteo)
    assert "whole tiles" in reason(jnp.float32, 1000, criteo)
    assert "fast memory" in reason(jnp.float32, 65_536, [(194_560, 39)])
    # here, on a CPU, Mosaic's kernels would be interpreted: XLA's products
    plan = (128, 256, None, 26624)
    assert not _linear_sgd._blocks_in_fast_memory(jnp.float32, 65_536, plan)
    monkeypatch.setattr(_mosaic, "interpret_mode", lambda: False)     # a TPU
    assert _linear_sgd._blocks_in_fast_memory(jnp.float32, 65_536, plan)
    assert not _linear_sgd._blocks_in_fast_memory(jnp.float32, 1000, plan)
    assert not _linear_sgd._blocks_in_fast_memory(jnp.float64, 65_536, plan)
    assert not _linear_sgd._blocks_in_fast_memory(jnp.float32, 65_536, ())
    assert not _linear_sgd._blocks_in_fast_memory(jnp.float32, 65_536, (None,) * 4)
    # neighbours of one shape are one group: four loops for eleven lengths
    assert [(g.members, g.first, g.c, g.rows, g.narrow) for g in sparse_blocks.walk(
        [(128, 11), (256, 10), (512, 1), (4096, 2), (5120, 1), (15360, 1), (26624, 8)])
    ] == [(((128, 11), (256, 10)), 0, 8, 32, True),
          (((512, 1), (4096, 2)), 21, 128, 32, True),
          (((5120, 1), (15360, 1)), 24, 128, 128, False),
          (((26624, 8),), 26, 128, 208, False)]


# -- the step -----------------------------------------------------------------

DIM, WIDTH, BS = 5000, 6, 16
#: Slots 0 and 1 share a block of 128 at 0 and slot 2's block of 256
#: overlaps it; slot 4 sits in one that passes ``dim`` (the last rows of
#: 128, zeros past the end); 3 and 5 gather. Starts in rows of 128.
MIXED_PLAN = (128, 128, 256, None, 1024, None)
MIXED_STARTS = np.asarray([0, 0, 0, 0, 32, 0], np.int32)
TOP = 32 * 128


def test_pallas_is_imported_beside_the_host_work_on_a_tpu_alone(monkeypatch):
    """A fit asks for Pallas early only where its step will trace a
    kernel and the import is still to do."""
    import sys
    import threading

    from flinkml_tpu.kernels import _mosaic

    started = []

    class Recorded:
        def __init__(self, **kw):
            started.append(kw)

        def start(self):
            pass

    monkeypatch.setattr(threading, "Thread", Recorded)
    _mosaic.import_beside_host_work()                 # a CPU
    assert started == []
    monkeypatch.setattr(_mosaic, "interpret_mode", lambda: False)
    monkeypatch.setitem(sys.modules, "jax.experimental.pallas", object())
    _mosaic.import_beside_host_work()                 # imported already
    assert started == []
    monkeypatch.delitem(sys.modules, "jax.experimental.pallas")
    _mosaic.import_beside_host_work()
    (thread,) = started
    assert thread["target"] is _mosaic._import_keeping_bytecode and thread["daemon"]


def test_the_early_import_keeps_its_bytecode_beside_the_compile_cache(
        tmp_path, monkeypatch):
    """Where the process keeps compiled programs, the modules a kernel's
    trace imports are compiled once a cache directory and read back by
    the processes after it (``sys.pycache_prefix`` while the import
    runs, put back whatever happens); with no cache directory the import
    is the interpreter's own."""
    import sys

    from flinkml_tpu.kernels import _mosaic

    (tmp_path / "src").mkdir()
    monkeypatch.syspath_prepend(str(tmp_path / "src"))
    before = sys.pycache_prefix, sys.dont_write_bytecode

    def compiled(name):
        (tmp_path / "src" / f"{name}.py").write_text("VALUE = 41 + 1\n")
        _mosaic._import_keeping_bytecode((name,))
        assert sys.modules.pop(name).VALUE == 42
        assert (sys.pycache_prefix, sys.dont_write_bytecode) == before
        return [p.name for p in (tmp_path / "cache").rglob(f"{name}.*.pyc")]

    from flinkml_tpu.utils import jax_cache

    monkeypatch.setattr(jax_cache, "in_use", lambda: str(tmp_path / "cache"))
    monkeypatch.setattr(sys, "dont_write_bytecode", True)   # as the chip's host
    before = sys.pycache_prefix, True
    assert len(compiled("kept_beside_the_programs")) == 1
    monkeypatch.setattr(jax_cache, "in_use", lambda: None)
    assert compiled("kept_nowhere") == []
    # a private module this JAX does not have is left to the lowering
    _mosaic._import_keeping_bytecode(("jax._src.pallas.no_such_module",))
    with pytest.raises(ImportError):
        _mosaic._import_keeping_bytecode(("no_such_public_module",))
    assert (sys.pycache_prefix, sys.dont_write_bytecode) == before


def _step_rows(rows, seed=1):
    rng = np.random.default_rng(seed)
    idx = np.stack([
        rng.integers(0, 100, rows), rng.integers(20, 128, rows),
        rng.integers(90, 250, rows), rng.integers(0, DIM, rows),
        rng.integers(TOP, DIM, rows), rng.integers(0, DIM, rows),
    ], axis=1).astype(np.int32)
    val = rng.standard_normal((rows, WIDTH)).astype(np.float32)
    y = (rng.random(rows) < 0.4).astype(np.float32)
    w = (rng.random(rows) + 0.5).astype(np.float32)
    return idx, val, y, w


def _run_step(mesh, loss, plan, data, coef, epoch, starts=None, bs=BS,
              check_vma=True):
    step = _linear_sgd.make_sparse_step_bucketed(
        loss, (bs,), "data", DIM, plan)
    if plan:
        data += (mesh.shard_batch(np.tile(starts, mesh.axis_size())),)
    f = jax.jit(jax.shard_map(
        lambda c, e, *placed: step(
            c, e, *placed, jnp.float32(0.3), jnp.float32(0.01),
            jnp.float32(0.001)),
        mesh=mesh.mesh, in_specs=(P(), P()) + (P("data"),) * len(data),
        out_specs=(P(), P()), check_vma=check_vma))
    new_coef, loss_value = f(coef, jnp.asarray(epoch, jnp.int32), *data)
    return np.asarray(new_coef), float(loss_value)


@pytest.fixture(params=PATHS)
def blocked(request, monkeypatch):
    """How the blocked step is run: ``(rows a device a step, keywords of
    :func:`_run_step`)``. ``xla``: the products of ``ops.sparse``, as a
    CPU runs the step. ``kernel``: the step as a TPU traces it, the two
    kernels of ``kernels.sparse_blocks`` interpreted (whole tiles of 128
    rows a device; an interpreted kernel's values carry no mesh axes, so
    the ``shard_map`` does not check them)."""
    if request.param == "xla":
        return BS, {}
    monkeypatch.setattr(
        _linear_sgd, "_blocks_in_fast_memory",
        lambda dtype, bs, plan: any(plan) and dtype == jnp.float32)
    return 128, {"bs": 128, "check_vma": False}


def _step_uses_the_kernels(plan, bs):
    step = _linear_sgd.make_sparse_step_bucketed(
        "logistic", (bs,), "data", DIM, plan)
    f32 = jnp.float32
    args = [jax.ShapeDtypeStruct((DIM,), f32), jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((3 * bs, WIDTH), jnp.int32),
            jax.ShapeDtypeStruct((3 * bs, WIDTH), f32),
            jax.ShapeDtypeStruct((3 * bs,), f32), jax.ShapeDtypeStruct((3 * bs,), f32)]
    if any(plan):                                   # the blocks' starts
        args.append(jax.ShapeDtypeStruct((WIDTH,), jnp.int32))
    args += [jax.ShapeDtypeStruct((), f32)] * 3
    program = jax.make_jaxpr(
        lambda *a: step(*a), axis_env=[("data", 1)])(*args)
    return len(_pallas_calls(program.jaxpr))


@pytest.mark.parametrize("loss", ["logistic", "hinge", "squared"])
def test_the_blocked_step_agrees_with_the_general_step(mesh, loss, blocked):
    bs, how = blocked
    p = mesh.axis_size()
    data = tuple(jax.device_put(a, NamedSharding(mesh.mesh, P("data")))
                 for a in _step_rows(p * 3 * bs))
    coef = jnp.asarray(
        np.random.default_rng(2).standard_normal(DIM).astype(np.float32))
    assert _step_uses_the_kernels(MIXED_PLAN, bs) == (2 if how else 0)
    for epoch in (0, 2):
        want, want_loss = _run_step(mesh, loss, (), data, coef, epoch, bs=bs)
        got, got_loss = _run_step(
            mesh, loss, MIXED_PLAN, data, coef, epoch, MIXED_STARTS, **how)
        assert np.abs(want - np.asarray(coef)).max() > 1e-3
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
        assert got_loss == pytest.approx(want_loss, rel=1e-6)


def test_blocks_that_start_elsewhere_run_the_same_step(mesh, blocked):
    """The starts are an operand: the cells moved by three rows of 128,
    and the coefficients with them, give the same step three rows on."""
    bs, how = blocked
    p = mesh.axis_size()
    idx, val, y, w = _step_rows(p * 3 * bs)
    low, top = TOP - 384, DIM - 384                # where slot 4's block goes
    for slot in (3, 5):                            # the gathered cells stay
        idx[:, slot] = 640 + idx[:, slot] % (low - 640)
    coef = np.random.default_rng(3).standard_normal(DIM).astype(np.float32)
    moved = coef.copy()
    moved[384:640], moved[:384] = coef[:256], coef[256:640]
    moved[low:top], moved[top:] = coef[TOP:], coef[low:TOP]
    shift = np.asarray([3, 3, 3, 0, -3, 0], np.int32)
    place = lambda *arrays: tuple(
        jax.device_put(a, NamedSharding(mesh.mesh, P("data"))) for a in arrays)
    want, want_loss = _run_step(
        mesh, "logistic", MIXED_PLAN, place(idx, val, y, w),
        jnp.asarray(coef), 1, MIXED_STARTS, **how)
    got, got_loss = _run_step(
        mesh, "logistic", MIXED_PLAN, place(idx + 128 * shift, val, y, w),
        jnp.asarray(moved), 1, MIXED_STARTS + shift, **how)
    assert got_loss == want_loss
    np.testing.assert_array_equal(got[384:640], want[:256])
    np.testing.assert_array_equal(got[low:top], want[TOP:])
    np.testing.assert_array_equal(got[640:low], want[640:low])


def test_a_step_the_kernels_do_not_take_is_the_step_as_it_was(monkeypatch):
    """Another dtype, a batch that is not whole tiles, the empty plan, a
    CPU: no kernel in the step, whatever else holds."""
    from flinkml_tpu.kernels import _mosaic

    assert _step_uses_the_kernels(MIXED_PLAN, 128) == 0          # a CPU
    monkeypatch.setattr(_mosaic, "interpret_mode", lambda: False)  # a TPU
    assert _step_uses_the_kernels(MIXED_PLAN, 128) == 2
    assert _step_uses_the_kernels(MIXED_PLAN, 100) == 0
    assert _step_uses_the_kernels((), 128) == 0
    assert _step_uses_the_kernels((None,) * WIDTH, 128) == 0


def test_a_plan_with_several_buckets_is_refused():
    with pytest.raises(ValueError, match="one-bucket"):
        _linear_sgd.make_sparse_step_bucketed(
            "logistic", (8, 8), "data", DIM, MIXED_PLAN)


def _products_through_xla(monkeypatch):
    """The two kernels' places in the step taken by ``ops.sparse``'
    products on the same operands (an interpreted kernel cannot run
    inside the trainer's ``shard_map``, which checks its values' mesh
    axes), and the step told it is on a TPU: what is run is how the
    trainer walks its plan for the kernels and takes their sums back."""
    def local_of(at, where, cells, starts, slots):
        rows = jnp.asarray(list(where[at:at + slots]))
        return cells[rows] - 128 * starts[rows][:, None], rows

    def lookup_dot(groups, where, blocks, cells, vals, starts):
        dot, at = 0.0, 0
        for (length, slots), block in zip(groups, blocks):
            local, rows = local_of(at, where, cells, starts, slots)
            dot = dot + jnp.sum(vals[rows] * sparse.block_lookup(
                block.reshape(slots, length), local), axis=0)
            at += slots
        return dot

    def accumulate(groups, where, cells, vals, starts, mult):
        sums, at = [], 0
        for length, slots in groups:
            local, rows = local_of(at, where, cells, starts, slots)
            sums.append(sparse.block_accumulate(
                local, vals[rows] * mult[None, :], length).reshape(slots, -1, 128))
            at += slots
        return sums

    monkeypatch.setattr(sparse_blocks, "lookup_dot", lookup_dot)
    monkeypatch.setattr(sparse_blocks, "accumulate", accumulate)
    monkeypatch.setattr(
        _linear_sgd, "_blocks_in_fast_memory",
        lambda dtype, bs, plan: any(plan) and dtype == jnp.float32)


def _fit_counting(fit):
    counters = metrics.group("trainer")
    before = counters.snapshot()["counters"].get("fused_block_fits")
    coef = fit()
    return coef, counters.snapshot()["counters"]["fused_block_fits"] - (before or 0.0)


def test_a_fit_counts_whether_its_loop_carried_the_kernels(mesh, monkeypatch):
    """``trainer.fused_block_fits``: counted at the loop, one a fit, 0.0
    where XLA's products ran (here, a CPU); and the fit whose step walks
    its plan as it does for the kernels is the fit."""
    indices = _field_rows(14, rows=2048)
    rows, width = indices.shape
    values = np.random.default_rng(14).standard_normal(indices.shape).astype(np.float32)
    y = (np.random.default_rng(15).random(rows) < 0.4).astype(np.float32)

    def fit():
        _linear_sgd._sparse_trainer_bucketed.cache_clear()
        return _linear_sgd.train_linear_model_sparse_csr(
            np.arange(rows + 1, dtype=np.int64) * width, indices.reshape(-1),
            values.reshape(-1), PLAN_DIM, y, None, loss="logistic", mesh=mesh,
            max_iter=4, learning_rate=0.5, global_batch_size=1024, reg=0.0,
            elastic_net=0.0, tol=0.0, seed=3)

    plain, counted = _fit_counting(fit)
    assert counted == 0.0
    _products_through_xla(monkeypatch)
    walked, counted = _fit_counting(fit)
    assert counted == 1.0
    _linear_sgd._sparse_trainer_bucketed.cache_clear()
    np.testing.assert_allclose(walked, plain, rtol=0, atol=1e-6)
    assert np.abs(plain).max() > 1e-2


def test_the_fused_share_reads_the_count_over_the_fits():
    """``benchmark/metrics/trainer.sparse_fused_block_share.json``
    through the benchmark's ``counter_ratio`` over a window's counters
    as ``benchmark/run.py`` flattens them, and its entry in
    ``BENCHMARK.json``."""
    import json
    import os

    from benchmark.readers import counter_ratio

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    name = "trainer.sparse_fused_block_share"
    with open(os.path.join(root, "benchmark", "metrics", f"{name}.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "counter_ratio"
    assert spec["params"] == {"num": "trainer.fused_block_fits", "den": "fits"}
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    # every cell whose fit may carry the kernels: the two one-chip LR
    # cells, and whatever cells later PRs append (PR 55's four-chip one)
    assert {"lr-criteo.fit", "lr-criteo.fit-cold"} <= set(entry.pop("workloads"))
    assert entry == {
        "name": name, "unit": "fits/fit", "better": "higher",
        "source": "program_counter", "layer": "Kernels",
        "moves": "fit_samples_per_s"}
    obs = {"setup_counters": {}, "units": {"fits": 17}}
    for fused, share in ((17.0, 1.0), (0.0, 0.0)):
        counters = {"trainer.fused_block_fits": fused, "trainer.steps": 2720.0}
        assert counter_ratio.read(spec["params"], {**obs, "counters": counters}) == share
    # a program without the count (the parent): no metric, no error
    assert counter_ratio.read(
        spec["params"], {**obs, "counters": {"trainer.steps": 2720.0}}) is None


# -- the planner --------------------------------------------------------------

CARDS, STRATUM, PLAN_DIM = (64, 3, 700, 5000, 40000), 2100, 12000
FIELD_PLAN = (128, 128, 1024, 3072, 3072)
#: In rows of 128: the fields start at 0, 2100, 4200, 6300, 8400.
FIELD_STARTS = [0, 16, 32, 49, 65]


def _field_rows(seed, rows=20000, offset=0):
    """One cell a field, ascending: field ``f`` owns columns ``[f *
    STRATUM, (f + 1) * STRATUM)`` (moved up by ``offset``), skewed to
    its head, large fields folded into the stratum
    (``benchmark/datagen_criteo``'s sampler)."""
    rng = np.random.default_rng(seed)
    card = np.asarray(CARDS)
    rank = np.floor(rng.random((rows, card.size)) ** 3 * card).astype(np.int64)
    return (rank % STRATUM + np.arange(card.size) * STRATUM
            + offset).astype(np.int32)


def _plan(indices, dim, step_rows=64):
    with ThreadPoolExecutor(4) as pool:
        plan, starts = sparse.slot_block_plan(indices, dim, step_rows, pool)
    return plan, None if starts is None else starts.tolist()


def test_two_seeds_of_a_field_blocked_table_give_one_plan():
    first, second = _plan(_field_rows(1), PLAN_DIM), _plan(_field_rows(2), PLAN_DIM)
    assert first == second == (FIELD_PLAN, FIELD_STARTS)
    for slot, (start, length) in enumerate(zip(FIELD_STARTS, FIELD_PLAN)):
        cells = np.concatenate([_field_rows(1)[:, slot], _field_rows(2)[:, slot]])
        assert 128 * start <= cells.min() and cells.max() < 128 * start + length


def test_tables_of_one_schema_share_a_plan_wherever_their_columns_start():
    """Another lowest category, a split that lost the head of a field:
    the plan (what keys the program) stays, the starts move."""
    plan, starts = _plan(_field_rows(1, offset=384), PLAN_DIM + 384)
    assert plan == FIELD_PLAN
    assert starts == [3, 19, 35, 52, 68]
    rows = _field_rows(1)
    rows[:, 0] = np.maximum(rows[:, 0], 5)        # the lowest ids never seen
    rows[:, 3] = np.maximum(rows[:, 3], 6310)
    assert _plan(rows, PLAN_DIM) == (FIELD_PLAN, FIELD_STARTS)


def test_a_block_that_would_pass_dim_is_moved_down_and_wide_slots_stay_general(
        monkeypatch):
    rows = _field_rows(3)
    plan, starts = _plan(rows, 5 * STRATUM)
    # 10,500 columns are 83 rows of 128, the block 24 of them.
    assert plan == FIELD_PLAN and starts == FIELD_STARTS[:4] + [83 - 24]
    monkeypatch.setattr(sparse, "BLOCK_MAX_COLUMNS", 1024)
    assert _plan(rows, PLAN_DIM) == ((128, 128, 1024, None, None),
                                     [0, 16, 32, 0, 0])


def test_a_one_hot_is_bounded_by_splitting_groups_and_leaving_long_blocks(
        monkeypatch):
    monkeypatch.setattr(sparse, "_BLOCK_ONE_HOT_ELEMENTS", 64 * 24)
    # 24 rows of 128 at most over 64 rows a step: 3072 columns.
    plan, _ = _plan(_field_rows(3), PLAN_DIM)
    assert plan == FIELD_PLAN
    assert _plan(_field_rows(3), PLAN_DIM, 65)[0][3:] == (None, None)
    assert sparse.block_groups(plan, 64) == [
        (128, [0, 1]), (1024, [2]), (3072, [3]), (3072, [4])]
    monkeypatch.setattr(sparse, "_BLOCK_ONE_HOT_ELEMENTS", 64 * 48)
    assert [slots for _, slots in sparse.block_groups(plan, 64)] == [
        [0, 1], [2], [3, 4]]
    assert sparse.block_groups((), 64) == []


def test_the_plan_of_a_table_in_tasks_is_the_plan_of_the_whole(monkeypatch):
    rows = _field_rows(4, rows=1000)
    whole = _plan(rows, PLAN_DIM)
    monkeypatch.setattr(sparse, "_PLAN_TASK_ROWS", 96)
    monkeypatch.setattr(sparse, "_PLAN_FOLD", 7)
    assert _plan(rows, PLAN_DIM) == whole
    lows, highs = sparse._column_ranges(rows[:5])
    np.testing.assert_array_equal(lows, rows[:5].min(axis=0))
    np.testing.assert_array_equal(highs, rows[:5].max(axis=0))


def _prepare(mesh, indptr, indices, dim, values=None):
    n = indptr.size - 1
    names = ("cells", "blocked_cells", "blocked_slots", "buckets")

    def read():
        counters = metrics.group("hostdata.sparse").snapshot()["counters"]
        return {k: counters.get(k, 0.0) for k in names}

    before = read()
    if values is None:
        values = np.ones(indices.size, np.float32)
    place, sizes, plan = _linear_sgd.prepare_sparse_buckets(
        indptr, indices, values, dim, np.zeros(n, np.float32), None, mesh, 64,
        seed=0)
    data = _linear_sgd._placed(place(0, 1))
    assert len(data) == 4 * len(sizes) + bool(plan)
    return plan, data, {k: v - before[k] for k, v in read().items()}


def test_rows_hashed_over_all_of_dim_have_the_empty_plan(mesh):
    """Slot ``j`` is the ``j``-th smallest of the row's hashes: over many
    rows it spans most of ``dim``."""
    rng = np.random.default_rng(5)
    dim, rows, width = 1_000_000, 4000, 8
    indices = np.sort(rng.integers(0, dim, (rows, width)), axis=1).astype(np.int32)
    indptr = np.arange(rows + 1, dtype=np.int64) * width
    plan, _, counted = _prepare(mesh, indptr, indices.reshape(-1), dim)
    assert plan == ()
    assert counted == {"cells": rows * width, "blocked_cells": 0.0,
                       "blocked_slots": 0.0, "buckets": 1.0}


def test_a_field_blocked_table_is_planned_and_counted(mesh):
    indices = _field_rows(6, rows=3000)
    rows, width = indices.shape
    indptr = np.arange(rows + 1, dtype=np.int64) * width
    plan, data, counted = _prepare(mesh, indptr, indices.reshape(-1), PLAN_DIM)
    assert plan == FIELD_PLAN
    starts = np.asarray(data[-1]).reshape(mesh.axis_size(), width)
    assert (starts == FIELD_STARTS).all()
    assert counted == {"cells": rows * width, "blocked_cells": rows * width,
                       "blocked_slots": width, "buckets": 1.0}


def test_another_training_dtype_has_no_plan(mesh):
    indices = _field_rows(8, rows=512)
    rows, width = indices.shape
    place, sizes, plan = _linear_sgd.prepare_sparse_buckets(
        np.arange(rows + 1, dtype=np.int64) * width, indices.reshape(-1),
        np.ones(indices.size), PLAN_DIM, np.zeros(rows), None, mesh, 64,
        dtype=jnp.bfloat16, seed=0)
    assert plan == () and len(_linear_sgd._placed(place(0, 1))) == 4


# -- ragged rows ----------------------------------------------------------------


def _ragged(indices, keep, values=None):
    indptr = np.zeros(indices.shape[0] + 1, np.int64)
    np.cumsum(keep.sum(axis=1), out=indptr[1:])
    return indptr, indices[keep], None if values is None else values[keep]


def _drop(shape, seed, share=0.05):
    """A mask with a cell in twenty missing, every slot hit, rows 0-2
    whole, row 3 empty."""
    keep = np.random.default_rng(seed).random(shape) >= share
    keep[:3] = True
    keep[3] = False
    return keep


def test_a_field_blocked_table_with_cells_missing_is_laid_one_field_a_slot():
    indices = _field_rows(7, rows=3000)
    values = np.random.default_rng(7).standard_normal(
        indices.shape).astype(np.float32)
    keep = _drop(indices.shape, 7)
    indptr, idx, val = _ragged(indices, keep, values)
    with ThreadPoolExecutor(3) as pool:
        block = sparse.align_ragged_rows(indptr, idx, val, np.float32, pool)
    starts = indices.min(axis=0)
    np.testing.assert_array_equal(
        block["indices"], np.where(keep, indices, starts))
    np.testing.assert_array_equal(block["values"], np.where(keep, values, 0))
    np.testing.assert_array_equal(block["slot_cells"], keep.sum(axis=0))
    assert block["indices"].dtype == np.int32
    assert block["values"].dtype == np.float32


def test_the_aligned_table_in_tasks_is_the_aligned_table(monkeypatch):
    indices = _field_rows(9, rows=1000)
    keep = _drop(indices.shape, 9)
    indptr, idx, _ = _ragged(indices, keep)
    val = np.ones(idx.size, np.float32)
    with ThreadPoolExecutor(3) as pool:
        whole = sparse.align_ragged_rows(indptr, idx, val, np.float32, pool)
        monkeypatch.setattr(sparse, "_ALIGN_TASK_ROWS", 96)
        tasks = sparse.align_ragged_rows(indptr, idx, val, np.float32, pool)
    for name in whole:
        np.testing.assert_array_equal(tasks[name], whole[name])


@pytest.mark.parametrize("case", ["hashed", "text", "unsorted", "two-in-a-field",
                                  "under-the-first-field"])
def test_rows_that_keep_to_no_fields_are_not_aligned(case):
    rng = np.random.default_rng(11)
    indices = _field_rows(11, rows=2000)
    keep = _drop(indices.shape, 11)
    if case == "hashed":
        indices = np.sort(rng.integers(0, PLAN_DIM, indices.shape), axis=1
                          ).astype(np.int32)
    elif case == "text":                      # widths 0 to 5: mostly padding
        keep = np.arange(indices.shape[1]) < rng.integers(
            0, 6, indices.shape[0])[:, None]
    elif case == "unsorted":
        indices[50] = indices[50, ::-1]
    elif case == "two-in-a-field":            # a row with field 1's cell twice
        indices[60, 2], keep[60] = indices[60, 1] + 1, True
        keep[60, 4] = False
    else:                                     # a cell under every widest row's
        indices[:, 0] += 7
        indices[70, 0], keep[70] = 2, True
        keep[70, 3] = False
    indptr, idx, _ = _ragged(indices, keep)
    with ThreadPoolExecutor(2) as pool:
        assert sparse.align_ragged_rows(
            indptr, idx, np.ones(idx.size, np.float32), np.float32, pool) is None


def test_a_field_blocked_table_with_cells_missing_is_planned(mesh):
    """A one-hot encoder that drops a category, a file that leaves zeros
    out: one missing cell does not cost the table its plan."""
    indices = _field_rows(7, rows=3000)
    keep = _drop(indices.shape, 7)
    indptr, idx, _ = _ragged(indices, keep)
    plan, data, counted = _prepare(mesh, indptr, idx, PLAN_DIM)
    assert plan == FIELD_PLAN
    assert counted == {"cells": keep.sum(), "blocked_cells": keep.sum(),
                       "blocked_slots": 5.0, "buckets": 1.0}
    assert data[0].shape[1] == 5


def test_ragged_rows_with_no_blocked_slot_keep_their_buckets(mesh, monkeypatch):
    indices = _field_rows(7, rows=3000)
    keep = _drop(indices.shape, 7)
    indptr, idx, _ = _ragged(indices, keep)
    monkeypatch.setattr(sparse, "BLOCK_MAX_COLUMNS", 0)
    plan, data, counted = _prepare(mesh, indptr, idx, PLAN_DIM)
    assert plan == () and counted["buckets"] > 1 and len(data) % 4 == 0


@pytest.mark.parametrize("loss", ["logistic", "squared"])
def test_the_fit_of_an_aligned_table_is_the_fit_of_its_buckets(
        mesh, loss, monkeypatch):
    """Ragged field rows through the plan against the same rows through
    the padded buckets and the general step: other batches a step (the
    buckets are stratified by width), so full-batch steps are compared."""
    indices = _field_rows(12, rows=512)
    values = np.random.default_rng(12).standard_normal(
        indices.shape).astype(np.float32)
    keep = _drop(indices.shape, 12, share=0.1)
    indptr, idx, val = _ragged(indices, keep, values)
    y = (np.random.default_rng(13).random(512) < 0.4).astype(np.float32)

    def fit():
        return _linear_sgd.train_linear_model_sparse_csr(
            indptr, idx, val, PLAN_DIM, y, None, loss=loss, mesh=mesh,
            max_iter=5, learning_rate=0.5, global_batch_size=512, reg=0.0,
            elastic_net=0.0, tol=0.0, seed=3)

    planned = fit()
    monkeypatch.setattr(sparse, "BLOCK_MAX_COLUMNS", 0)
    np.testing.assert_allclose(planned, fit(), rtol=0, atol=2e-6)
    assert np.abs(planned).max() > 1e-2


def test_the_one_hot_encoders_default_output_field_by_field_is_planned(mesh):
    """``OneHotEncoder`` as it comes (``dropLast``: a field's last
    category is the empty vector, so rows are ragged), its sparse
    outputs set side by side as one vector: every cell is blocked."""
    from flinkml_tpu.linalg import SparseVector
    from flinkml_tpu.models import LogisticRegression, OneHotEncoder
    from flinkml_tpu.table import CsrColumn, Table

    rng = np.random.default_rng(21)
    cards, rows = (5, 40, 300, 3, 700), 2000
    raw = {f"c{f}": np.floor(rng.random(rows) ** 2 * card)
           for f, card in enumerate(cards)}
    for f, card in enumerate(cards):
        raw[f"c{f}"][f] = card - 1                    # the last category is seen
    encoder = (OneHotEncoder().set_input_cols(list(raw))
               .set_output_cols([f"o{f}" for f in range(len(cards))])
               .set_output_format("sparse"))
    (encoded,) = encoder.fit(Table(raw)).transform(Table(raw))
    parts = [encoded[f"o{f}"] for f in range(len(cards))]
    sizes = [part[0].size() for part in parts]
    assert sizes == [card - 1 for card in cards]
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    vectors = [SparseVector(
        int(offsets[-1]),
        np.concatenate([part[r].indices + off for part, off in zip(parts, offsets)]),
        np.concatenate([part[r].values for part in parts]))
        for r in range(rows)]
    column = CsrColumn.from_vectors(vectors)
    widths = np.diff(column.indptr)
    assert widths.min() < widths.max() == len(cards)
    table = Table({"features": column,
                   "label": (raw["c1"] < 3).astype(np.float64)})
    counters = metrics.group("hostdata.sparse")
    before = dict(counters.snapshot()["counters"])
    LogisticRegression().set_seed(0).set_max_iter(3).set_global_batch_size(
        256).fit(table)
    added = {k: v - before.get(k, 0.0)
             for k, v in counters.snapshot()["counters"].items()}
    assert added["blocked_slots"] == len(cards)
    assert added["blocked_cells"] == added["cells"] == column.indices.size
    assert added["buckets"] == 1.0


# -- the program no plan reaches ------------------------------------------------


def _step_before_plans(loss, local_bss, axis, dim):
    """``make_sparse_step_bucketed`` as it was before plans (cb591e5)."""
    from flinkml_tpu.models._linear_sgd import (
        _acc_dt, _margin_grad, _soft_threshold, _window, ell_matvec)

    def step(coef, epoch, *rest):
        *blocks, learning_rate, reg_l2, reg_l1 = rest
        acc = _acc_dt(coef.dtype)
        contribs, flat_idx = [], []
        loss_l = jnp.zeros((), acc)
        wsum_l = jnp.zeros((), acc)
        for b, local_bs in enumerate(local_bss):
            idxl, vall, yl, wl = blocks[4 * b : 4 * (b + 1)]
            ib = _window(idxl, epoch, local_bs)
            vb = _window(vall, epoch, local_bs)
            yb = _window(yl, epoch, local_bs)
            wb = _window(wl, epoch, local_bs)
            dot = ell_matvec(ib, vb, coef)
            mult, per_ex = _margin_grad(loss, dot, yb, wb)
            contribs.append((vb * mult[:, None]).reshape(-1))
            flat_idx.append(ib.reshape(-1))
            loss_l = loss_l + jnp.sum(per_ex.astype(acc))
            wsum_l = wsum_l + jnp.sum(wb.astype(acc))
        grad_local = jax.ops.segment_sum(
            jnp.concatenate(contribs), jnp.concatenate(flat_idx),
            num_segments=dim,
        )
        grad = jax.lax.psum(grad_local, axis)
        loss_sum = jax.lax.psum(loss_l, axis)
        wsum = jax.lax.psum(wsum_l, axis)
        grad = grad + 2.0 * reg_l2 * coef
        loss_sum = loss_sum + reg_l2 * jnp.sum(jnp.square(coef.astype(acc)))
        step_size = learning_rate.astype(acc) / wsum
        new_coef = _soft_threshold(
            coef - step_size.astype(coef.dtype) * grad,
            step_size.astype(coef.dtype) * reg_l1,
        )
        return new_coef, (loss_sum / wsum).astype(coef.dtype)

    return step


@pytest.mark.parametrize("widths", [(5,), (3, 7, 12)])
@pytest.mark.parametrize("loss", ["logistic", "hinge", "squared"])
def test_the_trainer_under_the_empty_plan_lowers_to_the_text_before_plans(
        mesh, loss, widths):
    m = mesh.mesh
    rep, rows = NamedSharding(m, P()), NamedSharding(m, P("data"))

    def arg(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    f32 = jnp.float32
    args = [arg((300,), f32), arg((), jnp.int32), arg((), f32)]
    for width in widths:
        args += [arg((128, width), jnp.int32, rows), arg((128, width), f32, rows),
                 arg((128,), f32, rows), arg((128,), f32, rows)]
    args += [arg((), f32)] * 4 + [arg((), jnp.int32)]
    sizes = (8,) * len(widths)
    now = _linear_sgd._sparse_trainer_bucketed(m, loss, sizes, "data", 300)
    before = _linear_sgd._whole_loop(
        m, _step_before_plans(loss, sizes, "data", 300), 4 * len(widths), "data",
        # under the module's name of today: the phases it declares are in it
        "lr_sparse_loop", _linear_sgd.SPARSE_PHASES)
    text = now.lower(*args).as_text()
    assert text == before.lower(*args).as_text()
    assert "gather" in text and "dynamic_slice" not in text.replace(
        "stablehlo.dynamic_slice", "", len(widths) * 4)


def test_a_plan_is_part_of_the_trainers_cache_key_and_its_starts_are_not(mesh):
    def trainer(plan):
        return _linear_sgd._sparse_trainer_bucketed(
            mesh.mesh, "logistic", (BS,), "data", DIM, plan)

    assert trainer(()) is trainer(())
    assert trainer(MIXED_PLAN) is trainer(MIXED_PLAN)
    assert trainer(MIXED_PLAN) is not trainer(())
    indptr = np.arange(3001, dtype=np.int64) * 5
    plans = [_prepare(mesh, indptr, _field_rows(6, 3000, offset).reshape(-1),
                      PLAN_DIM + 384)
             for offset in (0, 384)]
    assert plans[0][0] == plans[1][0] == FIELD_PLAN
    assert not np.array_equal(plans[0][1][-1], plans[1][1][-1])
