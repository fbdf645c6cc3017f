"""Programs of the benchmark's main paths compiled FOR the chip, at their
real sizes, without the chip: the TPU's compiler is installed here and
compiles for a described v5e (``on-chip-measurement`` guide, section 2).
What interpret mode cannot show fails here: a Mosaic kernel the chip's
compiler refuses, a program that does not fit the device.

Nothing runs, so no result and no time is read. The topology is
described inside a fixture (never while a module is imported), and every
such test lives in this one file: only one process may hold the TPU's
library.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


def _phases(text: str) -> set:
    """The ``profiling.phase`` names in the ``op_name`` paths of a
    compiled program's instructions."""
    from .test_phases import _PHASE_IN_PATH

    return set(_PHASE_IN_PATH.findall(text))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("precision", ["HIGHEST", "DEFAULT"])
def test_knn_search_at_the_cells_size(one_chip, no_compile_cache, monkeypatch,
                                      precision):
    """``knn-mnist8m.transform``'s one program: 10,000 queries against
    2,025,000 x 784 resident rows, k 5, the product and the ranking in
    ONE Pallas kernel compiled by Mosaic (not interpreted), inside a
    v5e's 16 GB; at the model's precision and at the one bfloat16 pass of
    the benchmark's control."""
    from jax.experimental.layout import Format, Layout

    from flinkml_tpu.kernels import _mosaic
    from flinkml_tpu.models import knn

    monkeypatch.setattr(_mosaic, "interpret_mode", lambda: False)
    rows, dim, queries, k = 2_025_000, 784, 10_000, 5
    assert knn._ranks_in_the_product(
        jax.ShapeDtypeStruct((queries, dim), jnp.float32),
        jax.ShapeDtypeStruct((rows, dim), jnp.float32), k)
    assert knn.PRODUCT_PRECISION == jax.lax.Precision.HIGHEST

    def on_chip(shape, dtype):
        # As a v5e holds them (read off placed arrays there, PR 30): a
        # float32 [n, 784] array lies with its ROWS along the lanes.
        rows_minor = Layout(major_to_minor=tuple(reversed(range(len(shape)))))
        return jax.ShapeDtypeStruct(shape, dtype, sharding=Format(rows_minor, one_chip))

    # Under the suite's x64, as a user with ``jax_enable_x64`` on calls it:
    # the program is traced in 32-bit mode all the same. One 64-bit block
    # inside a kernel and Mosaic ABORTS the process, so the traced
    # program is read first and a failure here is an assertion.
    with jax.enable_x64(True):
        traced = knn._knn_vote.trace(
            on_chip((queries, dim), jnp.float32), on_chip((rows, dim), jnp.float32),
            on_chip((rows,), jnp.float32), on_chip((rows,), jnp.int32),
            k=k, num_classes=10, chunk=knn._chunk_rows(queries, knn.KnnModel.CHUNK),
            tile=knn._tile_rows(rows, k),
            precision=getattr(jax.lax.Precision, precision))
        wide = [line.strip() for line in str(traced.jaxpr).splitlines()
                if re.search(r"\b[fiu]64\[", line)]
        assert wide == []
        compiled = traced.lower().compile()
    assert compiled.as_text().count("tpu_custom_call") == 1   # one kernel
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert 0.25 * 16e9 < held < 0.5 * 16e9
    # no [chunk, tile] block of distances (0.44 GB before the kernel), let
    # alone the [queries, rows] matrix (81 GB) or a relaid copy of the
    # train set (6.35 GB): the padded queries, their norms, the answers
    assert memory.temp_size_in_bytes < 0.3e9


@pytest.mark.parametrize("x64", [False, True])
def test_top_k_kernel_compiles_whatever_x64_says(one_chip, no_compile_cache, x64):
    """``kernels.topk.pallas_top_k`` by itself (as the KNN search's tiled
    path calls it): its ``pallas_call`` holds no 64-bit value under x64 (Mosaic
    refuses an int64 block index and aborts on a float64 block), so it
    compiles in both modes."""
    from flinkml_tpu.kernels import topk

    with jax.enable_x64(x64):
        traced = jax.jit(lambda x: topk.pallas_top_k(x, 5, interpret=False)).trace(
            jax.ShapeDtypeStruct((64, 4096), jnp.float32, sharding=one_chip))
        assert not re.search(r"\b[fiu]64\[", str(traced.jaxpr))
        compiled = traced.lower().compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_kmeans_whole_loop_at_the_cells_size(topo, no_compile_cache):
    """``kmeans-mnist8m.fit``'s one program: twenty Lloyd rounds over
    2,025,000 x 784 resident float32 rows, k 10, both products at
    ``HIGHEST``, on a one-chip mesh (the ``psum`` included). XLA fuses the
    argmin into the distances' product and the one-hot into the sums', so
    beside the resident rows, norms and mask the program holds a few
    megabytes: no ``[rows, k]`` array (81 MB, 1.04 GB padded to 128
    lanes), no bfloat16 parts of the table (9.5 GB), no relaid copy of it
    (6.35 GB)."""
    import numpy as np
    from jax.experimental.layout import Format, Layout
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from flinkml_tpu.models import kmeans

    rows, dim, k = 2_025_000, 784, 10
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    by_rows, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    # As a v5e holds it (read off the placed array there, PR 32): the
    # table lies with its ROWS along the lanes.
    table = jax.ShapeDtypeStruct(
        (rows, dim), jnp.float32,
        sharding=Format(Layout(major_to_minor=(1, 0)), by_rows))
    per_row = jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=by_rows)
    assert kmeans.PRODUCT_PRECISION == jax.lax.Precision.HIGHEST
    compiled = kmeans._kmeans_trainer(mesh, k, "data").trace(
        table, per_row, per_row,
        jax.ShapeDtypeStruct((k, dim), jnp.float32, sharding=whole),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=whole)).lower().compile()
    text = compiled.as_text()
    assert text.count("operand_precision={highest,highest}") == 2
    assert "tpu_custom_call" not in text          # XLA's lowering, no kernel
    assert _phases(text) == set(kmeans.PHASES)
    memory = compiled.memory_analysis()
    assert 0.39 * 16e9 < memory.argument_size_in_bytes < 0.41 * 16e9
    assert memory.temp_size_in_bytes < 0.05e9


#: ``fm-criteo.fit``'s slot plan: what ``ops.sparse.slot_block_plan`` reads
#: off ``datagen_criteo``'s rows under ``benchmark/configs/fm-criteo.json``
#: (field ``f`` on ``min(cardinality, 25,641)`` columns from ``25,641 f``),
#: up the ladder of block lengths: 269,696 block columns over 39 slots.
FM_CRITEO_PLAN = (
    128, 128, 256, 256, 128, 256, 256, 128, 256, 256, 128, 256, 256, 2048, 1024,
    26624, 26624, 512, 128, 13312, 1024, 128, 26624, 6144, 26624, 4096, 128,
    15360, 26624, 128, 6144, 3072, 128, 26624, 256, 128, 26624, 256, 26624)


@pytest.mark.parametrize("precision", ["HIGHEST", "DEFAULT"])
def test_fm_adam_loop_at_the_cells_size(topo, no_compile_cache, precision):
    """``fm-criteo.fit``'s one program: Adam's whole run over 16,777,216 x
    39 resident cells, ``dim`` 1,000,000, 16 factors, batch 65,536, under
    the cell's slot plan, on a one-chip mesh (the ``psum`` included), in
    32-bit mode; at the program's precision and at the one bfloat16 pass
    of the benchmark's control. XLA's lowering, no kernel. Beside the
    resident table the program holds its parameter table, two moments and
    the gradient as ``[17, 7813, 128]`` arrays (68 MB each: laid ``[dim,
    17]`` or ``[17, dim]`` a v5e pads the 17 to 128 lanes, 512 MB each)
    and the walk's operands, under a gigabyte in all."""
    import numpy as np
    from jax.experimental.layout import Format, Layout
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from flinkml_tpu.models import _fm_sparse

    rows, width, dim, k, batch = 16_777_216, 39, 1_000_000, 16, 65_536
    assert len(FM_CRITEO_PLAN) == width and sum(FM_CRITEO_PLAN) == 269_696
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    by_rows, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    # As a v5e holds them (PR 30): an ELL table lies with its ROWS along
    # the lanes.
    rows_minor = Format(Layout(major_to_minor=(1, 0)), by_rows)

    def on(shape, dtype, sharding=whole):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    f32, i32 = jnp.float32, jnp.int32
    assert _fm_sparse.LOOKUP_PRECISION == jax.lax.Precision.HIGHEST
    with jax.enable_x64(False):
        compiled = _fm_sparse._trainer(
            mesh, True, batch, "data", FM_CRITEO_PLAN,
            getattr(jax.lax.Precision, precision)).trace(
            on((1,), f32), on((k + 1, _fm_sparse.padded_dim(dim) // 128, 128), f32),
            on((rows, width), i32, rows_minor), on((rows, width), f32, rows_minor),
            on((rows,), f32, by_rows), on((rows,), f32, by_rows),
            on((width,), i32), on((), f32), on((), f32), on((), i32),
            on((), f32)).lower().compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text          # XLA's lowering, no kernel
    # every product of the walk at the precision asked for
    assert (text.count("operand_precision={highest,highest}") > 0) == (
        precision == "HIGHEST")
    assert _phases(text) == set(_fm_sparse.PHASES)
    memory = compiled.memory_analysis()
    # the cells, labels and weights: 5.37 GB, a third of the chip
    assert 0.33 * 16e9 < memory.argument_size_in_bytes < 0.36 * 16e9
    assert memory.temp_size_in_bytes < 1.0e9
    assert memory.output_size_in_bytes < 0.1e9    # 17 x 7813 x 128 floats


def test_fm_handover_at_the_cells_size(one_chip, no_compile_cache):
    """``fm-criteo.fit``'s hand-over (PR 56): the learned ``[17, 7813,
    128]`` table turned to ``w [7813, 128]`` and ``V [125008, 128]`` (a
    column's 16 factors side by side) on the device. Both results lie in
    rows of 128 lanes, the host's own order, so the read-back is a plain
    copy; handed out as ``[dim, 16]`` the compiler relabels the ``[16,
    dim]`` it had (layout ``{0,1}``) and leaves the turn to the host
    again. The turn passes through ``[dim, 16]`` lane-padded eightfold:
    half a gigabyte of temporaries, once a fit, beside a table of 5.4 GB
    (and under the loop's own peak: ``memory_peak_bytes`` did not move)."""
    from flinkml_tpu.models import _fm_sparse

    dim, k = 1_000_000, 16
    table = jax.ShapeDtypeStruct(
        (k + 1, _fm_sparse.padded_dim(dim) // 128, 128), jnp.float32, sharding=one_chip)
    compiled = _fm_sparse._handover.lower(table).compile()
    text = compiled.as_text()
    assert "fm_handover" in text.splitlines()[0]
    assert ("(f32[7813,128]{1,0:T(8,128)}, f32[125008,128]{1,0:T(8,128)}) tuple("
            in text)
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes < 0.07e9        # 17 x 1,000,064 floats
    assert memory.temp_size_in_bytes < 0.6e9


@pytest.mark.parametrize("chips, rows, steps", [
    (1, 16_777_216, 160), (4, 16_777_216, 160), (4, 4 * 11_460_155, 700)],
    ids=["lr-criteo.fit", "lr-criteo.fit-4chips", "lr-criteo-dp4.fit"])
def test_lr_sparse_loop_at_the_cells_size_holds_the_block_kernels(
        topo, no_compile_cache, monkeypatch, chips, rows, steps):
    """``lr-criteo.fit``'s one program, ``lr_sparse_loop``: 160 steps of
    65,536 rows over 16,777,216 x 39 resident cells, ``dim`` 1,000,000,
    under the cell's slot plan (``fm-criteo.fit``'s: the same rows), on a
    one-chip mesh as the cell runs it and on the host's four chips (a
    quarter of the rows and of the batch each, the gradient's ``psum``
    after the kernels); and ``lr-criteo-dp4.fit``'s (PR 55): the whole
    file's 45,840,617 rows, padded to 11,460,155 a chip (a shard that is
    no whole number of its 16,384-row windows), 700 steps, the ``psum``
    under its own phase, ``lr.psum``, which a mesh of one compiles to
    nothing. On a TPU the 39 blocked slots' products are
    ``kernels.sparse_blocks``' two kernels, compiled here by Mosaic (not
    interpreted): one ``[65,536, 128]`` product of a slot in HBM is 33.5
    MB, and the program's temporaries stay under what five of them would
    take. Compiled UNDER x64, as the suite runs: the kernels
    are traced in 32-bit mode whatever the flag says, and the traced
    program is read for 64-bit blocks first (Mosaic aborts on one).
    Since PR 58 the lookup's two wide bodies multiply int8 digits of the
    floats' bits into int32; four int32 planes of a 26,624-column block
    still leave the tile at 4,096 batch rows at 65,536 and at 16,384 rows
    a device (at 512 a long slot read 0.59 ms for 0.49), which is
    asserted here and not assumed."""
    import numpy as np
    from jax.experimental.layout import Format, Layout
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from flinkml_tpu.kernels import _mosaic, sparse_blocks
    from flinkml_tpu.models import _linear_sgd
    from flinkml_tpu.ops import sparse

    monkeypatch.setattr(_mosaic, "interpret_mode", lambda: False)
    width, dim, batch = 39, 1_000_000, 65_536
    assert _linear_sgd._blocks_in_fast_memory(jnp.float32, batch // chips,
                                              FM_CRITEO_PLAN)
    groups = sparse_blocks.walk([
        (length, len(slots)) for length, slots in
        sparse.block_groups(FM_CRITEO_PLAN, batch // chips)])
    # four traced bodies for eleven lengths: narrow 8, narrow 128, wide
    # 128, wide 208
    assert [(g.c, g.rows, g.narrow, g.slots) for g in groups] == [
        (8, 32, True, 21), (128, 32, True, 6), (128, 128, False, 4),
        (128, 208, False, 8)]
    assert sparse_blocks.tile_rows(batch // chips, groups) == sparse_blocks.TILE == 4096
    mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
    by_rows, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    # As a v5e holds them (PR 30): an ELL table lies with its ROWS along
    # the lanes.
    rows_minor = Format(Layout(major_to_minor=(1, 0)), by_rows)

    def on(shape, dtype, sharding=whole):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    f32, i32 = jnp.float32, jnp.int32
    _linear_sgd._sparse_trainer_bucketed.cache_clear()   # keyed by no backend
    try:
        with jax.enable_x64(True):
            traced = _linear_sgd._sparse_trainer_bucketed(
                mesh, "logistic", (batch // chips,), "data", dim,
                FM_CRITEO_PLAN).trace(
                on((dim,), f32), on((), i32), on((), f32),
                on((rows, width), i32, rows_minor), on((rows, width), f32, rows_minor),
                on((rows,), f32, by_rows), on((rows,), f32, by_rows),
                on((chips * width,), i32, by_rows),
                on((), f32), on((), f32), on((), f32), on((), f32), np.int32(steps))
            kernels = [eqn for eqn in _pallas_calls(traced.jaxpr.jaxpr)]
            assert len(kernels) == 2                    # the lookup, the accumulation
            # a product a body: the lookup's wide two in int8 digits
            assert [_product_operands(k) for k in kernels] == [
                ["bfloat16"] * 2 + ["int8"] * 2, ["bfloat16"] * 4]
            wide = [str(v.aval) for eqn in kernels
                    for v in eqn.params["jaxpr"].invars + eqn.params["jaxpr"].outvars
                    if re.search(r"[fiu]64", str(v.aval))]
            assert wide == []
            compiled = traced.lower().compile()
    finally:
        _linear_sgd._sparse_trainer_bucketed.cache_clear()
    text = compiled.as_text()
    assert "lr_sparse_loop" in text and text.count("tpu_custom_call") == 2
    # the collective's phase holds an operation where there is a collective
    assert _phases(text) == set(_linear_sgd.SPARSE_PHASES) - (
        {"lr.psum"} if chips == 1 else set())
    assert ("all-reduce" in text) == (chips > 1)
    memory = compiled.memory_analysis()
    # the cells, labels and weights: 328 B a row as the chip tiles them
    # (a row's 39 slots on 40 sublanes): 5.5 GB, a third of one chip; the
    # whole file 3.76 GB a chip, 23.5 %
    assert (0.99 * 328 * rows < chips * memory.argument_size_in_bytes
            < 1.01 * 328 * rows + 64e6)
    assert memory.temp_size_in_bytes < 5 * batch * 128 * 4


@pytest.mark.parametrize("chips", [1, 4])
def test_fm_adam_loop_at_the_cells_size_holds_the_payload_kernels(
        topo, no_compile_cache, monkeypatch, chips):
    """``fm-criteo.fit``'s one program as a TPU traces it (PR 53): the 39
    blocked slots' rows looked up and their gradient accumulated by
    ``kernels.payload_blocks``' kernels, two a direction (the 21 slots of
    up to 256 columns in one call, the 18 longer ones walked in chunks in
    the other), compiled here by Mosaic (not interpreted), on a one-chip
    mesh as the cell runs it and on the host's four chips (a quarter of
    the rows and of the batch each, the gradient's ``psum`` after the
    kernels). The looked-up rows ``xp [39, 17, batch]`` are the one long
    operand a step keeps: the program's temporaries stay under 0.6 GB
    where XLA's walk keeps a gigabyte. Traced under x64, as the suite
    runs: the kernels are traced in 32-bit mode whatever the flag says."""
    import numpy as np
    from jax.experimental.layout import Format, Layout
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from flinkml_tpu.kernels import _mosaic
    from flinkml_tpu.models import _fm_sparse

    monkeypatch.setattr(_mosaic, "interpret_mode", lambda: False)
    rows, width, dim, k, batch = 16_777_216, 39, 1_000_000, 16, 65_536
    assert _fm_sparse._walk_in_fast_memory(
        jnp.float32, batch // chips, FM_CRITEO_PLAN, k + 1,
        _fm_sparse.LOOKUP_PRECISION)
    mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
    by_rows, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    rows_minor = Format(Layout(major_to_minor=(1, 0)), by_rows)

    def on(shape, dtype, sharding=whole):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    f32, i32 = jnp.float32, jnp.int32
    _fm_sparse._trainer.cache_clear()
    try:
        with jax.enable_x64(True):
            traced = _fm_sparse._trainer(
                mesh, True, batch // chips, "data", FM_CRITEO_PLAN).trace(
                on((1,), f32), on((k + 1, _fm_sparse.padded_dim(dim) // 128, 128), f32),
                on((rows, width), i32, rows_minor), on((rows, width), f32, rows_minor),
                on((rows,), f32, by_rows), on((rows,), f32, by_rows),
                on((width,), i32), on((), f32), on((), f32), on((), i32),
                on((), f32))
            kernels = list(_pallas_calls(traced.jaxpr.jaxpr))
            assert len(kernels) == 4
            wide = [str(v.aval) for eqn in kernels
                    for v in eqn.params["jaxpr"].invars + eqn.params["jaxpr"].outvars
                    if re.search(r"[fiu]64", str(v.aval))]
            assert wide == []
            compiled = traced.lower().compile()
    finally:
        _fm_sparse._trainer.cache_clear()
    text = compiled.as_text()
    assert "fm_adam_loop" in text and text.count("tpu_custom_call") == 4
    assert "operand_precision={highest,highest}" not in text   # no product of XLA's
    assert _phases(text) == set(_fm_sparse.PHASES)
    memory = compiled.memory_analysis()
    # the cells, labels and weights, 5.37 GB over the chips, and a table each
    assert 0.33 * 16e9 < chips * memory.argument_size_in_bytes < 0.37 * 16e9
    assert memory.temp_size_in_bytes < 0.6e9


@pytest.mark.parametrize("slots,tile", [(36, 4096), (76, 1024), (129, 128)])
def test_payload_kernels_take_many_short_slots_at_a_smaller_tile(
        one_chip, no_compile_cache, slots, tile):
    """The short kernels alone where a table has many narrow fields: a
    grid step holds a tile of EVERY short slot, so Mosaic refused 76
    slots of 256 columns x 17 floats at a tile of 4,096 rows (64.25 MiB
    of its 64; PR 53's review). ``short_tile_rows`` halves the tile
    before that: the most slots it leaves each tile compile here, and
    one slot more than 129 falls back to XLA's walk."""
    from flinkml_tpu.kernels import payload_blocks

    payload, batch, lengths = 17, 65_536, [256] * slots
    assert payload_blocks.unsupported_reason(jnp.float32, batch, lengths, payload) is None
    assert payload_blocks.short_tile_rows(batch, payload, slots, 256, slots) == tile
    assert payload_blocks.short_tile_rows(batch, payload, 130, 256, 130) is None

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cells = (on((slots, batch), jnp.int32), on((slots, batch), jnp.float32),
             on((slots,), jnp.int32))
    with jax.enable_x64(True):
        lookup = jax.jit(lambda table, c, v, at: payload_blocks.lookup(
            lengths, range(slots), table, c, v, at, interpret=False)).lower(
                on((payload, 7813, 128), jnp.float32), *cells).compile()
        accumulate = jax.jit(lambda c, v, at, m, base, xp: payload_blocks.accumulate(
            lengths, range(slots), c, v, at, m, base, [xp], interpret=False)).lower(
                *cells, on((batch,), jnp.float32), on((payload, batch), jnp.float32),
                on((slots, payload, batch), jnp.float32)).compile()
    assert lookup.as_text().count("tpu_custom_call") == 1
    assert accumulate.as_text().count("tpu_custom_call") == 1


def test_sparse_block_kernels_take_criteo_laid_out_field_by_field(
        one_chip, no_compile_cache):
    """The two kernels alone at another ladder of block lengths: Criteo
    field by field (PR 29: five fields on blocks of up to 194,560
    columns, 1,520 rows of 128), whose digits (the lookup's wide body,
    int8 into int32 since PR 58: 6,080 rows of them a block) and sums
    stay in fast memory while a long block's product is cut in tiles of
    512 batch rows."""
    from flinkml_tpu.kernels import sparse_blocks

    groups, width, batch = [(8_192, 32), (59_392, 2), (194_560, 5)], 39, 65_536
    assert sparse_blocks.unsupported_reason(jnp.float32, batch, groups) is None
    assert sparse_blocks.tile_rows(batch, sparse_blocks.walk(groups)) == 512

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    blocks = [on((slots, length // 128, 128), jnp.float32) for length, slots in groups]
    cells = (on((width, batch), jnp.int32), on((width, batch), jnp.float32),
             on((width,), jnp.int32))
    with jax.enable_x64(True):
        traced = jax.jit(lambda b, c, v, at: sparse_blocks.lookup_dot(
            groups, range(width), b, c, v, at, interpret=False)).trace(
                blocks, *cells)
        # three wide bodies (rooms of 128, 464 and 1,520 rows), no narrow one
        (kernel,) = _pallas_calls(traced.jaxpr.jaxpr)
        assert _product_operands(kernel) == ["int8"] * 3
        lookup = traced.lower().compile()
        accumulate = jax.jit(lambda c, v, at, m: sparse_blocks.accumulate(
            groups, range(width), c, v, at, m, interpret=False)).lower(
                *cells, on((batch,), jnp.float32)).compile()
    assert lookup.as_text().count("tpu_custom_call") == 1
    assert accumulate.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("loss,rows,dim,batch,tile", [
    ("logistic", 9_437_184, 123, 262_144, 4096),      # lr-a9a.fit's
    ("hinge", 9_437_184, 123, 262_144, 4096),
    ("squared", 9_437_184, 123, 262_144, 4096),
    ("logistic", 1_048_576, 1020, 8_192, 2048),       # several rows of lanes
    ("logistic", 262_144, 2048, 65_536, 1024),        # the widest it takes
])
def test_dense_step_kernel_at_the_cells_size(one_chip, no_compile_cache,
                                             monkeypatch, loss, rows, dim,
                                             batch, tile):
    """``kernels.dense_step.margin_grad`` alone, compiled by Mosaic for
    a described v5e: ``lr-a9a.fit``'s window (262,144 x 123 of 9,437,184
    resident rows, tiles of 4,096, every loss: one body each) and the
    widths either side of it that ``unsupported_reason`` lets through.
    UNDER x64: the kernel is traced in 32-bit mode whatever the flag
    says (one float64 block and Mosaic aborts the process, so the traced
    program is read first). The table is an operand as it lies: the
    compiled program holds no second array."""
    from flinkml_tpu.kernels import _mosaic, dense_step

    monkeypatch.setattr(_mosaic, "interpret_mode", lambda: False)
    assert dense_step.unsupported_reason(jnp.float32, rows, batch, dim) is None
    assert dense_step.tile_rows(batch, dim) == tile

    def on(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with jax.enable_x64(True):
        traced = jax.jit(lambda x, y, w, c, at: dense_step.margin_grad(
            loss, x, y, w, c, at, batch, interpret=False)).trace(
                on((rows, dim)), on((rows,)), on((rows,)), on((dim,)),
                on((), jnp.int32))
        (kernel,) = _pallas_calls(traced.jaxpr.jaxpr)
        inner = kernel.params["jaxpr"]
        assert [str(v.aval) for v in inner.invars + inner.outvars
                if re.search(r"[fiu]64", str(v.aval))] == []
        compiled = traced.lower().compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * dim * 128 * 4


def test_which_tables_lie_with_their_features_along_the_lanes(one_chip,
                                                              no_compile_cache):
    """``dense_step.features_along_lanes``, the one thing the kernel's
    selection cannot read off its operands, against what the chip's
    compiler does: a float32 ``[n, dim]`` table is row-major where that
    pads it no more than the other way (the last row of lanes filled to
    within eight), and lies with its ROWS along the lanes elsewhere
    (``knn-mnist8m``'s 784 columns, PR 30): a kernel handed such a table
    by its rows would have the whole of it re-laid first."""
    from flinkml_tpu.kernels import dense_step

    widths = [1, 8, 9, 100, 120, 121, 123, 127, 128, 129, 136, 249, 250, 256,
              257, 500, 505, 784, 1000, 1017, 1024, 1535, 2000, 2041, 2048]
    for rows in (65_536, 9_437_184):
        for dim in widths if rows < 1e6 else (123,):
            table = jax.ShapeDtypeStruct((rows, dim), jnp.float32, sharding=one_chip)
            compiled = jax.jit(lambda x: jnp.sum(x, axis=0)).lower(table).compile()
            ((held,), _) = compiled.input_formats
            assert (held.layout.major_to_minor == (0, 1)) == (
                dense_step.features_along_lanes(dim)), (rows, dim, held)


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` of ``jaxpr``, inner programs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


def _product_operands(kernel):
    """The operand dtypes of every product a kernel (a ``pallas_call``)
    traces, loops' bodies included, sorted."""
    def dots(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                yield str(eqn.invars[0].aval.dtype)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from dots(sub)

    return sorted(dots(kernel.params["jaxpr"]))


@pytest.mark.parametrize("step", ["kernel", "xla"])
def test_lr_dense_loop_is_one_program_for_a_chunk_and_for_a_hit(
        topo, no_compile_cache, monkeypatch, step):
    """``lr-a9a.fit``'s one program, ``lr_dense_loop``, at the cell's size
    (9,437,184 x 123 float32 rows, batch 262,144, on a one-chip mesh),
    with the step as a TPU traces it (``kernel``: ``kernels.dense_step``'s
    one Mosaic kernel, compiled here and not interpreted, the rows read
    in place: no slice and no copy of them, the labels' reshape a
    bitcast, under x64 as the suite runs, traced in 32-bit mode all the
    same) and as every other backend does (``xla``: two products). A
    fit that places its table enters it a chunk at a time, each chunk
    from the carry the chunk before returned, to the step the landed
    rows allow; a fit that finds its placement kept with its ``Table``
    (PR 37) enters it once, from a carry replicated on the mesh, to
    ``max_iter``. Both are the same avals on the same shardings, so both
    lower to the one program set-up's fit compiled: the carry goes in as
    it comes out, and where the loop ends is an operand. The data is not
    donated: the table keeps the arrays the loop read."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from flinkml_tpu.kernels import _mosaic
    from flinkml_tpu.models import _linear_sgd

    rows, dim, batch, max_iter = 9_437_184, 123, 262_144, 72
    if step == "kernel":
        monkeypatch.setattr(_mosaic, "interpret_mode", lambda: False)
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    by_rows, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())

    def on(shape, dtype, sharding=whole):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    f32 = jnp.float32
    _linear_sgd._dense_trainer.cache_clear()             # keyed by no backend
    trainer = _linear_sgd._dense_trainer(mesh, "logistic", batch, "data")
    carry = (on((dim,), f32), on((), jnp.int32), on((), f32))
    data = (on((rows, dim), f32, by_rows), on((rows,), f32, by_rows),
            on((rows,), f32, by_rows))
    hy = (on((), f32),) * 4

    def entered(to_step):
        # as _run_chunked hands it over: a host int32, not a device array
        traced = trainer.trace(*carry, *data, *hy, np.int32(to_step))
        kernels = list(_pallas_calls(traced.jaxpr.jaxpr))
        assert len(kernels) == (step == "kernel")
        # one 64-bit block inside a kernel and Mosaic ABORTS the process
        assert [str(v.aval) for eqn in kernels
                for v in eqn.params["jaxpr"].invars + eqn.params["jaxpr"].outvars
                if re.search(r"[fiu]64", str(v.aval))] == []
        return traced.lower()

    try:
        with jax.enable_x64(step == "kernel"):
            chunk, hit = entered(5), entered(max_iter)
            assert chunk.as_text() == hit.as_text()
            compiled = hit.compile()
    finally:
        _linear_sgd._dense_trainer.cache_clear()
    text = compiled.as_text()
    assert "lr_dense_loop" in text
    assert text.count("tpu_custom_call") == (step == "kernel")
    if step == "kernel":
        # the rows go to the kernel as the table holds them: no copy, no
        # slice, no product of XLA's over them
        assert not re.search(r"f32\[\d+,123\]\S* (copy|dynamic-slice|fusion)\(", text)
        assert compiled.memory_analysis().temp_size_in_bytes < 1e6
    (args, _), out = compiled.input_shardings, compiled.output_shardings
    assert all(a.is_equivalent_to(o, c.ndim)
               for a, o, c in zip(args[:3], out, carry))
    memory = compiled.memory_analysis()
    # the rows (123 columns padded to the 128 lanes), labels and weights
    assert 0.28 * 16e9 < memory.argument_size_in_bytes < 0.31 * 16e9
    assert memory.alias_size_in_bytes == 0      # nothing donated


def _yahoomusic_degrees(side: str):
    """Ratings a target at ``als-yahoomusic``'s counts, from the laws of
    ``benchmark/datagen_ratings.py`` (the users' exactly; the items' the
    expectation of the draw, rounded)."""
    import numpy as np

    from benchmark import datagen_ratings as gen

    users, items, ratings = 1_000_990, 624_961, 252_800_275
    if side == "user":
        return gen.user_degrees(users, ratings), items
    law = (np.arange(items) + gen.ITEM_SHIFT) ** -gen.ITEM_SKEW
    expected = ratings * (gen.ITEM_FLAT / items + (1 - gen.ITEM_FLAT) * law / law.sum())
    return np.rint(expected).astype(np.int64), users


@pytest.mark.parametrize("fetch", ["gather", "row_fetch"])
@pytest.mark.parametrize("side", ["user", "item"])
def test_als_half_step_at_the_cells_size(topo, no_compile_cache, monkeypatch, side, fetch):
    """``als-yahoomusic.fit``'s program of one side: a half-step over
    252,800,275 ratings at rank 100 under the side's plan (chunks of
    262,144 slots), the lane solver's Mosaic kernel included, on a
    one-chip mesh (the ``all_gather`` included), in 32-bit mode; with
    every slot's row through XLA's gather, and as the cell runs it since
    PR 50: ``kernels.row_fetch`` a chunk (65,536 hot rows in fast memory
    under its own limit, whatever XLA fuses around the call) and the
    gather over a chunk's cold ids alone, a block of 4,096 at a time into
    the loop's one buffer (here three slots in ten; a tile's DMA 1,024
    rows). The compiled program's own memory
    analysis holds that no ``[targets, k, k]`` (40 GB of users) and no
    ``[ratings, k, k]`` exists: beside the side's slots (2.5 GB) and the
    fixed side's factors it holds the solved rows twice (padded to 128
    lanes for the next half-step, and as the model keeps them) and a
    chunk's scratch."""
    import os
    import sys

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from flinkml_tpu.kernels import _mosaic, row_fetch
    from flinkml_tpu.models import _als_blocked

    monkeypatch.setattr(_mosaic, "interpret_mode", lambda: False)
    degrees, fixed_rows = _yahoomusic_degrees(side)
    rank = 100
    plan = _als_blocked.plan_side(degrees, 1, _als_blocked._CHUNK_SLOTS)
    assert 1.1 * degrees.sum() < plan.slots_local < 1.3 * degrees.sum()
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    by_rows, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())

    def on(shape, dtype, sharding=whole):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    f32, i32 = jnp.float32, jnp.int32
    loops = _als_blocked._loops(plan.plan)
    fetch_plan, fetch_args = None, ()
    if fetch == "row_fetch":
        fetch_plan = (row_fetch.HOT_ROWS, 1024)
        fetch_args = (
            on((3 * plan.slots_local // 10 // row_fetch.BLOCK * row_fetch.BLOCK,),
               i32, by_rows),
            on((sum(turns for _, turns in loops) + 1,), i32, by_rows),
            on((sum(turns * row_fetch.tiles_of(n) for n, turns in loops),), i32, by_rows),
            on((row_fetch.HOT_ROWS,), i32))
    assert _als_blocked.GRAM_PRECISION == jax.lax.Precision.HIGHEST
    with jax.enable_x64(False):
        traced = _als_blocked._program(
            mesh, plan.plan, rank, False, _als_blocked.GRAM_PRECISION, True,
            fetch_plan).trace(
            on((plan.slots_local,), i32, by_rows), on((plan.slots_local,), f32, by_rows),
            on((plan.rows_local,), f32, by_rows),
            on((plan.owner.shape[1],), i32, by_rows), on((degrees.size,), i32),
            *fetch_args,
            on((fixed_rows + 1, 128), f32), on((), f32), on((), f32))
        assert not re.search(r"\b[fis]64\b", str(traced.jaxpr))   # Mosaic lowers none
        compiled = traced.lower().compile()
    text = compiled.as_text()
    # every bucket's product at the precision the configuration states
    assert text.count("operand_precision={highest,highest}") >= len(loops)
    assert _phases(text) == set(_als_blocked.PHASES)
    # the solver a loop, and the fetch kernel a loop where it is taken
    assert text.count("tpu_custom_call") == len(loops) * (1 + (fetch == "row_fetch"))
    memory = compiled.memory_analysis()
    slots_and_fixed = (8 * plan.slots_local + 512 * (fixed_rows + 1)
                       + sum(4 * a.shape[0] for a in fetch_args))
    assert slots_and_fixed < memory.argument_size_in_bytes < slots_and_fixed + 0.1e9
    # [targets + 1, 128] and [targets, 100] float32
    assert memory.output_size_in_bytes < 1.0e9
    assert memory.temp_size_in_bytes < 2.0e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 12e9


@pytest.mark.parametrize("slots", [262_144, 261_888, 24])
def test_the_row_fetch_kernel_at_the_cells_size(one_chip, no_compile_cache, slots):
    """``kernels.row_fetch.fetch`` as Mosaic compiles it (not
    interpreted) at ``als-yahoomusic``'s shapes: 65,536 hot rows of 128
    lanes in ONE buffer of fast memory with two tiles of cold rows behind
    them (34 MiB, inside the kernel's own limit and a v5e's 128 MiB), a
    tile of 2,048 slots, runs of up to 1,024 cold rows read by one DMA; a
    chunk of whole tiles, one that ends inside its last tile (a bucket of
    1,023 targets of 256 slots), and one shorter than a tile."""
    from flinkml_tpu.kernels import row_fetch

    cap = 1024 if slots > row_fetch.TILE else 8
    tiles = row_fetch.tiles_of(slots)
    fetch = jax.jit(lambda loc, starts, hot, cold: row_fetch.fetch(
        loc, starts, hot, cold, cap=cap, interpret=False))
    with jax.enable_x64(True):
        compiled = fetch.trace(
            jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((tiles,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((row_fetch.HOT_ROWS, 128), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((row_fetch.cold_rows(slots, cap), 128), jnp.float32,
                                 sharding=one_chip),
        ).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert f'"size":"{row_fetch.VMEM_LIMIT_BYTES}"' in text
    assert (row_fetch.HOT_ROWS + 2 * row_fetch.TILE) * 512 < row_fetch.VMEM_LIMIT_BYTES
    # nothing but the padding of the slots' local indices to whole tiles
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * row_fetch.TILE + 4096


@pytest.mark.parametrize("entries", [300, 1536, 16_384, 98_304])
def test_the_sorted_row_update_at_the_cells_table(one_chip, no_compile_cache, entries):
    """``kernels.row_update.add_rows_sorted`` as Mosaic compiles it (not
    interpreted) on ``w2v-1bw``'s ``[1,115,016, 384]`` table: a group of
    eight rows is a DMA's slice, the table is aliased through and nothing
    else is held; at the cell's two lists and at lists shorter than a
    tile (the chip tiles a vector of int32 by 1,024: an ids block of
    another length is refused)."""
    from flinkml_tpu.kernels import row_update

    rows, lanes = 1_115_016, 384
    update = jax.jit(
        lambda t, i, r: row_update.add_rows_sorted(t, i, r, interpret=False),
        donate_argnums=0)
    with jax.enable_x64(True):
        compiled = update.trace(
            jax.ShapeDtypeStruct((rows, lanes), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((entries,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((entries, lanes), jnp.float32, sharding=one_chip),
        ).lower(lowering_platforms=("tpu",)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == rows * lanes * 4
    assert memory.temp_size_in_bytes < 8 * 2048 * lanes      # a short list's padding


@pytest.mark.parametrize("scores,updates", [
    ("float32", "sorted"), ("bfloat16", "sorted"), ("float32", "scattered")])
def test_w2v_whole_fit_at_the_cells_size_holds_no_vocabulary_sized_temporary(
        one_chip, no_compile_cache, monkeypatch, scores, updates):
    """``w2v-1bw.fit``'s one program, ``w2v_sgns_loop``: 256 steps of 16,384
    pairs over the 805,306,368-token corpus and two ``[1,115,016, 384]``
    tables (1,115,011 words in whole groups of eight rows), as the program
    and as the benchmark's control rounds the products' operands. The tables
    are donated and updated where they lie: beside its arguments the program
    holds the draw's frames and the batch's rows (0.38 GB; 0.41 with the
    contributions in sorted order), never a ``[vocab, dim]`` array (1.7 GB;
    the dense trainer's two gradients a step were 2.7 GB), and the whole
    stays inside a v5e's 16 GB. ``sorted``, what a TPU runs: Mosaic accepts
    ``kernels.row_update`` twice a step (``v``'s centres, ``u``'s contexts
    and negatives as one list) at these shapes, aliased through. Every
    gather stays XLA's own, and ``scattered`` (every other backend's step,
    compiled for the chip) the three scatter-adds too: a gather of slices
    the compiler cannot fetch as rows is expanded into a ``while`` of its
    own (65,536 turns a step, read off this program as first written)."""
    from flinkml_tpu.kernels import _mosaic, row_update
    from flinkml_tpu.models import _w2v_table

    monkeypatch.setattr(_mosaic, "interpret_mode", lambda: False)
    vocab, dim, tokens = 1_115_016, 300, 805_306_368
    assert row_update.unsupported_reason(jnp.float32, vocab, 384) is None
    # 70.27 % of the tokens survive at the cell's corpus: its candidates
    d = _w2v_table.Draw(16_384, 5, 5, _w2v_table.candidates_a_step(
        16_384, tokens, int(0.7027 * 65536 * tokens)), tokens, 100_000_000)
    assert d.candidates == 35_072
    rows = -(-(tokens + 2 * d.span) // 128) + 1
    lanes = _w2v_table.padded_dim(dim)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with jax.enable_x64(True):    # as a user with ``jax_enable_x64`` on calls it
        traced = _w2v_table._program(
            d, None if scores == "float32" else jnp.bfloat16,
            updates == "sorted").trace(
            on_chip((vocab, lanes), jnp.float32), on_chip((vocab, lanes), jnp.float32),
            on_chip((rows, 128), jnp.int32), on_chip((rows, 128), jnp.uint16),
            on_chip((100_000_000,), jnp.int32), on_chip((), jnp.uint32),
            on_chip((), jnp.float32), on_chip((), jnp.int32))
        # (a Python number is a weak 64-bit SCALAR under x64 until it meets
        # its array; an ARRAY of 64-bit values would be emulated on the chip)
        wide = [line.strip() for line in str(traced.jaxpr).splitlines()
                if re.search(r"\b[fiu]64\[\d", line)]
        assert wide == []
        compiled = traced.lower().compile()
    text = compiled.as_text()
    assert len(re.findall(r"= \([^\n]*\) while\(", text)) == 1
    assert text.count("tpu_custom_call") == (2 if updates == "sorted" else 0)
    assert _phases(text) == set(
        _w2v_table.PHASES if updates == "sorted" else _w2v_table.PHASES_UNSORTED)
    memory = compiled.memory_analysis()
    table = vocab * lanes * 4
    assert memory.temp_size_in_bytes < 0.3 * table
    assert memory.alias_size_in_bytes >= 2 * table       # both updated in place
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert 0.5 * 16e9 < held < 0.65 * 16e9


@pytest.mark.parametrize("features,nodes", [(13, 1), (13, 8), (13, 32), (13, 128), (100, 16)])
def test_gbt_level_kernel_at_the_cells_size(one_chip, no_compile_cache, features, nodes):
    """``kernels.gbt_hist`` by itself over ``gbt-airline``'s 115,343,360 x
    13 one-byte bins: a level of 1 node and of 8 (FOLDED, PR 48: a one-hot
    of 128 rows against 96 columns of one MXU tile, a feature's own, the
    packed bfloat16 rows masked as 32-bit words), of 32 (depth 6's last,
    folded: 384 columns, three MXU tiles) and of 128 (depth 8's last, the
    most it takes at 13 features, not folded), and the widest level it takes
    of a table of 100 features (16 nodes, not folded; ``vmem_bytes`` against
    its limit: Mosaic has to agree). It takes the
    uint8 block (the array's own count of rows), the 32-bit copy in scratch
    whose rows a ``fori_loop`` over the features reads by a dynamic sublane,
    and the product that contracts the lanes of both operands; beside its
    arguments the program holds the sums and their re-ordered copy."""
    from flinkml_tpu.kernels import gbt_hist

    rows = 115_343_360 if features == 13 else 1 << 22
    assert gbt_hist.fold(nodes) == (nodes in (1, 8, 32))
    assert gbt_hist.tile_rows(rows) == gbt_hist.TILE
    assert gbt_hist.vmem_bytes(features, nodes, gbt_hist.TILE) <= gbt_hist.VMEM_LIMIT_BYTES
    if (features, nodes) in ((13, 128), (100, 16)):     # the widest it takes
        assert gbt_hist.vmem_bytes(features, 2 * nodes, gbt_hist.TILE) > (
            gbt_hist.VMEM_LIMIT_BYTES)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with jax.enable_x64(True):
        traced = jax.jit(lambda b, g, h, n: gbt_hist.level_histograms(
            b, g, h, n, nodes, interpret=False)).trace(
            on_chip((features, rows), jnp.uint8), on_chip((rows,), jnp.float32),
            on_chip((rows,), jnp.float32), on_chip((rows,), jnp.int32))
        assert not re.search(r"\b[fiu]64\[", str(traced.jaxpr))
        sums = "f32[%d,%d,%d]" % (features, gbt_hist.one_hot_rows(nodes),
                                  gbt_hist.columns(nodes))
        assert sums in str(traced.jaxpr)             # the layout the rule names
        compiled = traced.lower().compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 64e6
    if features == 13:
        # the bins lie 13 rows in 16: 1.85 GB, and three rows of 0.46 GB
        assert 16 * rows + 12 * rows <= memory.argument_size_in_bytes < 17 * rows + 12 * rows


@pytest.mark.parametrize("histograms", ["kernel", "xla"])
def test_gbt_forest_at_the_cells_size_holds_no_rows_by_features_array_wider_than_a_byte(
        topo, no_compile_cache, monkeypatch, histograms):
    """``gbt-airline.fit``'s one program, ``gbt_forest``: two trees of depth
    6 over 115,343,360 x 13 bins, on a one-chip mesh (the ``psum``
    included), with the Mosaic product a level (what a TPU runs: six
    kernels, the two trees one ``scan``) and with XLA's chunked product
    (every other backend's, compiled for the chip). Neither makes an array
    of ``rows x features`` entries wider than a byte (the parent's three
    were 6 GB each a level), nor one of ``rows x bins``; beside the table,
    the labels and the weights the program holds rows of 0.46 GB
    (prediction, gradients, hessians, node, the draw's), inside a v5e's 16
    GB."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from flinkml_tpu.kernels import _mosaic
    from flinkml_tpu.models import _gbt_table
    from flinkml_tpu.parallel import DeviceMesh

    monkeypatch.setattr(_mosaic, "interpret_mode", lambda: False)
    rows, features = 115_343_360, 13
    mesh = Mesh(np.array(topo.devices[:1]), (DeviceMesh.DATA_AXIS,))

    def on_mesh(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, P(*spec)))

    with jax.enable_x64(True):    # as a user with ``jax_enable_x64`` on calls it
        traced = _gbt_table._program(
            mesh, DeviceMesh.DATA_AXIS, features, 256, 6, 2, True, True, 0, False,
            (histograms == "kernel",) * 6).trace(
            on_mesh((features, rows), jnp.uint8, None, DeviceMesh.DATA_AXIS),
            on_mesh((rows,), jnp.float32, DeviceMesh.DATA_AXIS),
            on_mesh((rows,), jnp.float32, DeviceMesh.DATA_AXIS),
            *[on_mesh((), jnp.float32)] * 4, on_mesh((2,), jnp.uint32))
        compiled = traced.lower().compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == (6 if histograms == "kernel" else 0)
    assert _phases(text) == set(_gbt_table.PHASES)
    itemsize = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2, "u16": 2}
    wide = set()
    for dtype, dims in re.findall(r"\b(pred|[suf]\d+|bf16)\[([\d,]+)\]", text):
        entries = np.prod([int(n) for n in dims.split(",")], dtype=np.float64)
        if entries >= rows * features and itemsize.get(dtype, 4) > 1:
            wide.add(f"{dtype}[{dims}]")
    assert wide == set()
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert 0.25 * 16e9 < held < 0.75 * 16e9


@pytest.mark.parametrize("precision", ["mixed", None])
def test_mlp_fit_at_the_cells_size(topo, no_compile_cache, precision):
    """``mlp-mnist8m.fit``'s one program, ``mlp_fit``: 124 steps of
    784-2500-2000-1500-1000-500-10 over windows of 16,384 of 2,025,000 x
    784 float32 rows on a one-chip mesh (the ``psum`` included), under
    ``mixed`` (the cell's: every product bfloat16 operands into a float32
    sum) and with no policy (float32 at ``HIGHEST``: the benchmark's better
    side). The rows lie as a v5e holds them, rows along the lanes, and the
    first product and its gradient read the window so: no copy of the
    table and no turned window. Under ``mixed`` the compiler casts the
    WHOLE table to bfloat16 ahead of the loop (3.18 GB of temporaries, a
    step then reads a 25.7 MB window; an ``optimization_barrier`` on the
    window does not hold it back, PR 52); both fit a v5e's 16 GB beside
    the table."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from flinkml_tpu.models import _mlp_table
    from flinkml_tpu.parallel import DeviceMesh
    from flinkml_tpu.precision import resolve_policy

    layers, rows, batch, steps = (784, 2500, 2000, 1500, 1000, 500, 10), 2_025_000, 16_384, 124
    mesh = Mesh(np.array(topo.devices[:1]), (DeviceMesh.DATA_AXIS,))

    def on_mesh(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, P(*spec)))

    f32 = jnp.float32
    params = tuple(s for a, b in zip(layers, layers[1:])
                   for s in (on_mesh((a, b), f32), on_mesh((b,), f32)))
    _mlp_table._trainer.cache_clear()                    # keyed by no backend
    try:
        trainer = _mlp_table._trainer(mesh, layers, True, batch, DeviceMesh.DATA_AXIS,
                                      steps, resolve_policy(precision))
        with jax.enable_x64(True):    # as the suite runs; the operands are float32
            compiled = trainer.trace(
                params, on_mesh((rows, 784), f32, DeviceMesh.DATA_AXIS),
                on_mesh((rows,), jnp.int32, DeviceMesh.DATA_AXIS),
                on_mesh((rows,), f32, DeviceMesh.DATA_AXIS),
                np.float32(1e-3), np.float32(0.0)).lower().compile()
    finally:
        _mlp_table._trainer.cache_clear()
    text = compiled.as_text()
    assert "mlp_fit" in text
    assert _phases(text) == set(_mlp_table.PHASES)
    table = compiled.input_formats[0][1]
    assert table.layout.major_to_minor == (1, 0)          # rows along the lanes
    assert not re.search(r"f32\[2025000,784\]\S* (copy|transpose)\(", text)
    assert not re.search(r"\[784,16384\]", text)          # no window turned
    assert ("bf16[2025000,784]" in text) == (precision == "mixed")
    assert "f64[" not in text
    memory = compiled.memory_analysis()
    assert 0.39 * 16e9 < memory.argument_size_in_bytes < 0.41 * 16e9
    assert memory.temp_size_in_bytes < (4.2e9 if precision == "mixed" else 1.2e9)
    assert memory.alias_size_in_bytes == 0      # the table keeps what the loop read
