"""``kernels/row_update``: a table's rows updated in sorted order, a group
of eight read, added to and written once, INTERPRETED on the CPU (the
kernel's DMAs, semaphores and scalar lists as the interpreter runs them)
against XLA's ``table.at[ids].add(rows)``.

*Equal to the bit, and why*: the kernel adds a row's entries to the row
one after another in the sorted list's order, in float32, which is what
a CPU's scatter-add does on the same list; and a stable sort keeps a
row's entries in the order the unsorted list had them, so
:func:`row_update.add_rows` equals the scatter-add on the unsorted list
too. (On a TPU XLA's own scatter-add sums collisions in an order of its
own: there the two are float32's rounding of a run's sum apart.)
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from flinkml_tpu.kernels import _mosaic, row_update  # noqa: E402

ROWS, LANES = 1000, 384


def _case(name: str, rng):
    """``(ids, tile)``: a case's ids and the entries a grid step holds
    (None: the kernel's own)."""
    if name == "a row named 300 times":
        ids = np.concatenate([rng.integers(0, ROWS, 700), np.full(300, 77)])
        return ids, None
    if name == "runs and groups that span a tile's end":
        # rows 40..47 are one group, 150 entries each way of a tile's end
        ids = np.concatenate([rng.integers(0, 40, 90), np.full(70, 43),
                              rng.integers(40, 48, 60), rng.integers(48, ROWS, 100)])
        return ids, 64
    if name == "a group's eight rows all named":
        return np.concatenate([np.arange(64, 72), np.arange(64, 72),
                               rng.integers(0, ROWS, 50)]), None
    if name == "the table's last group":
        return np.concatenate([np.arange(ROWS - 8, ROWS),
                               np.full(5, ROWS - 1), [0]]), None
    if name == "a tile of one distinct id":
        return np.concatenate([np.full(16, 3), np.full(48, 9),
                               rng.integers(10, ROWS, 20)]), 16
    if name == "entries fewer than a tile":
        return rng.integers(0, ROWS, 13), None
    if name == "more chunks of groups than the ring holds":
        return rng.permutation(ROWS)[:200], None
    if name == "whole chunks of groups, then one group more":
        # a tile of 16 entries: two whole chunks; the last tile: one group
        chunks = 2 * row_update.CHUNK
        return np.concatenate([np.arange(chunks) * 8 + 3, [ROWS - 2]]), chunks
    raise KeyError(name)


CASES = ["a row named 300 times", "runs and groups that span a tile's end",
         "a group's eight rows all named", "the table's last group",
         "a tile of one distinct id", "entries fewer than a tile",
         "more chunks of groups than the ring holds",
         "whole chunks of groups, then one group more"]


@pytest.mark.parametrize("case", CASES)
def test_the_sorted_update_is_the_scatter_add_to_the_bit(case):
    rng = np.random.default_rng(CASES.index(case))
    ids, tile = _case(case, rng)
    ids = np.sort(ids).astype(np.int32)
    table = rng.normal(size=(ROWS, LANES)).astype(np.float32)
    rows = rng.normal(size=(ids.size, LANES)).astype(np.float32)
    want = np.asarray(jnp.asarray(table).at[ids].add(rows))
    update = jax.jit(lambda t, i, r: row_update.add_rows_sorted(t, i, r, tile=tile))
    got = np.asarray(update(table, ids, rows))
    assert np.array_equal(got, want)
    untouched = np.setdiff1d(np.arange(ROWS), ids)
    assert untouched.size and np.array_equal(got[untouched], table[untouched])
    assert np.array_equal(np.asarray(update(table, ids, rows)), got)   # run after run


def test_the_semaphores_bytes_add_up_and_no_buffer_is_raced_for():
    """The generic interpreter above ignores a DMA's wait. Mosaic's own
    (``pltpu.InterpretParams``) counts a semaphore's bytes as the chip
    does, holds a copy back until it is waited for and follows every
    buffer's readers and writers: a chunk's reads signal ONE semaphore
    and one wait takes the bytes of all of them, its writes another; the
    last chunk's groups are waited for one by one. A wait that asks for
    more than was signalled never returns (hence the thread), one that
    asks for less leaves a buffer raced for."""
    import threading

    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as mosaic
    from jax.experimental.pallas import tpu as pltpu

    rng = np.random.default_rng(5)
    # more chunks than the ring holds and a part of one in the first tile,
    # a few groups in the last
    ids = np.sort(np.concatenate([
        rng.permutation(ROWS)[:230], np.full(30, 501), [8, 9, 17, 30, 30, 30, 30, 31]
    ])).astype(np.int32)
    table = rng.normal(size=(ROWS, LANES)).astype(np.float32)
    rows = rng.normal(size=(ids.size, LANES)).astype(np.float32)
    want = np.asarray(jnp.asarray(table).at[ids].add(rows))
    how = pltpu.InterpretParams(dma_execution_mode="on_wait", detect_races=True)
    got = []
    run = threading.Thread(daemon=True, target=lambda: got.append(np.asarray(
        jax.jit(lambda t, i, r: row_update.add_rows_sorted(
            t, i, r, tile=256, interpret=how))(table, ids, rows))))
    run.start()
    run.join(timeout=300)
    assert not run.is_alive(), "a wait for bytes that no copy signals"
    assert np.array_equal(got[0], want)
    assert not mosaic.races.races_found


def test_ids_in_any_order_are_sorted_stably_and_give_the_scatter_adds_bits():
    """The step's form: (id, position) sorted, the contributions fetched
    in that order. Entries of one row keep their order, so the sums are
    the unsorted scatter-add's; and under x64, as a user with
    ``jax_enable_x64`` on calls it, nothing of the kernel is 64 bits
    wide."""
    rng = np.random.default_rng(11)
    ids = np.concatenate([rng.integers(0, ROWS, 400), np.full(120, 200),
                          np.full(80, 7)]).astype(np.int32)
    rng.shuffle(ids)
    table = rng.normal(size=(ROWS, LANES)).astype(np.float32)
    rows = rng.normal(size=(ids.size, LANES)).astype(np.float32)
    want = np.asarray(jnp.asarray(table).at[ids].add(rows))
    assert jax.config.jax_enable_x64
    got = jax.jit(row_update.add_rows)(table, ids, rows)
    assert got.dtype == jnp.float32 and np.array_equal(np.asarray(got), want)


def test_the_table_is_updated_where_it_lies():
    """Donated and aliased through the kernel; beside the table the
    program makes no ``[rows, lanes]`` array."""
    rng = np.random.default_rng(3)
    ids = rng.integers(0, ROWS, 100).astype(np.int32)
    rows = jnp.asarray(rng.normal(size=(100, LANES)).astype(np.float32))
    table = jnp.zeros((ROWS, LANES), jnp.float32)
    update = jax.jit(row_update.add_rows, donate_argnums=0)
    traced = update.trace(table, ids, rows)
    made = [v.aval.shape for eqn in traced.jaxpr.eqns for v in eqn.outvars]
    assert made.count((ROWS, LANES)) == 1          # the kernel's own output
    (kernel,) = [e for e in traced.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert dict(kernel.params["input_output_aliases"]) == {2: 0}
    assert "tf.aliasing_output" in traced.lower().as_text()
    out = update(table, ids, rows)
    assert table.is_deleted() and out.shape == (ROWS, LANES)


@pytest.mark.parametrize("case", [
    ("a TPU, float32, 384 lanes, whole groups", None),
    ("a CPU", "not a TPU"),
    ("bfloat16 tables", "bfloat16"),
    ("300 lanes", "300 floats"),
    ("rows off a multiple of 8", "1115011 rows"),
    ("4 devices", "4 devices"),
], ids=lambda c: c[0])
def test_where_the_kernel_applies_is_read_off_the_table(case, monkeypatch):
    name, why = case
    if name != "a CPU":
        monkeypatch.setattr(_mosaic, "interpret_mode", lambda: False)     # a TPU
    dtype = jnp.bfloat16 if name == "bfloat16 tables" else jnp.float32
    rows = 1_115_011 if name == "rows off a multiple of 8" else 1_115_016
    lanes = 300 if name == "300 lanes" else 384
    devices = 4 if name == "4 devices" else 1
    reason = row_update.unsupported_reason(dtype, rows, lanes, devices)
    assert (reason is None) if why is None else (why in reason)


def test_the_sorted_update_share_reads_the_count_over_the_steps():
    """``benchmark/metrics/w2v.sorted_update_share.json`` through the
    benchmark's ``counter_ratio`` over a window's counters as
    ``benchmark/run.py`` flattens them, and its entry in
    ``BENCHMARK.json``."""
    from benchmark.readers import counter_ratio

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    name = "w2v.sorted_update_share"
    with open(os.path.join(root, "benchmark", "metrics", f"{name}.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "counter_ratio"
    assert spec["params"] == {"num": "w2v.sorted_update_steps", "den": "w2v.steps"}
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": "steps/step", "better": "higher",
        "source": "program_counter", "layer": "Word2Vec trainer",
        "moves": "fit_samples_per_s", "workloads": ["w2v-1bw.fit"]}
    obs = {"setup_counters": {}, "units": {"fits": 3, "steps": 768}}
    for went, share in ((768.0, 1.0), (0.0, 0.0)):
        counters = {"w2v.sorted_update_steps": went, "w2v.steps": 768.0}
        assert counter_ratio.read(spec["params"], {**obs, "counters": counters}) == share
    # a program without the count (the parent): no metric, no error
    assert counter_ratio.read(
        spec["params"], {**obs, "counters": {"w2v.steps": 768.0}}) is None
