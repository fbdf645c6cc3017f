"""``kernels/row_fetch``: a table's rows fetched by local index, the hot
rows out of one buffer in fast memory and each tile's run of cold rows
behind them, INTERPRETED on the CPU (the kernel's DMAs, semaphores and
SMEM tiles as the interpreter runs them) against XLA's
``table.at[ids].get()``.

*Equal to the bit, and why*: the kernel copies float32 rows. What a case
can get wrong is WHICH row: a local index that names another tile's run,
a run that starts off a group of eight, a tile that ends before the
call does.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from flinkml_tpu.kernels import _mosaic, row_fetch  # noqa: E402

ROWS, LANES, TILE = 600, 128, 1024


def _case(name: str, rng):
    """``(ids [calls, n], hot ids)`` of a case: the rows the slots name
    and the rows held hot (the zero row, ``ROWS - 1``, last)."""
    skewed = lambda n: np.minimum(rng.zipf(1.4, n) - 1, ROWS - 1)  # noqa: E731
    if name == "skewed ids, whole tiles":
        return skewed((3, 2 * TILE)), np.r_[0:63, ROWS - 1]
    if name == "a call that ends inside a tile":
        return skewed((2, 2 * TILE + 264)), np.r_[0:31, ROWS - 1]
    if name == "a call shorter than a tile":
        return skewed((4, 24)), np.r_[0:7, ROWS - 1]
    if name == "no hot slot":
        return rng.integers(100, ROWS - 1, (2, TILE + 8)), np.r_[0:15, ROWS - 1]
    if name == "every slot hot":
        return rng.integers(0, 32, (2, TILE)), np.r_[0:63, ROWS - 1]
    if name == "more hot rows than the table has":
        hot = np.full(1024, ROWS - 1)
        hot[:ROWS - 1] = np.arange(ROWS - 1)
        return skewed((2, TILE + 512)), hot
    if name == "a tile of cold slots alone":
        ids = skewed((1, 3 * TILE))
        ids[0, TILE:2 * TILE] = rng.integers(300, ROWS - 1, TILE)
        return ids, np.r_[0:15, ROWS - 1]
    if name == "runs that end off a group of eight":
        ids = np.zeros((1, 4 * TILE), np.int64)
        for t, cold in enumerate((1, 7, 9, 0)):
            ids[0, t * TILE + rng.permutation(TILE)[:cold]] = rng.integers(300, 500, cold)
        return ids, np.r_[0:7, ROWS - 1]
    raise KeyError(name)


CASES = ["skewed ids, whole tiles", "a call that ends inside a tile",
         "a call shorter than a tile", "no hot slot", "every slot hot",
         "more hot rows than the table has", "a tile of cold slots alone",
         "runs that end off a group of eight"]


BLOCK = 256


def _fetch(table, ids, hot_ids, tile=TILE, unroll=None):
    """Every call of ``ids`` through :func:`row_fetch.localize`, the
    blocks of its cold rows (:func:`row_fetch.fetch_cold`, into ONE
    buffer handed from call to call, as a program's loop does) and the
    interpreted kernel: ``(rows [calls, n, LANES], the layout)``."""
    hot_ids = np.asarray(hot_ids, np.int32)
    loc = np.empty(ids.shape, np.int32)
    local = row_fetch.localize(ids.astype(np.int32), row_fetch.ranks(hot_ids, ROWS),
                               hot_ids.size, loc, tile, BLOCK)
    cap = max(row_fetch.GROUP, local.run)
    first = np.cumsum(local.lengths) - local.lengths
    with jax.enable_x64(False):
        # (a block more: a program's list is never empty)
        table = jnp.asarray(table)
        cold = jnp.asarray(np.concatenate([local.cold, np.zeros(BLOCK, np.int32)]))
        hot = table[hot_ids]
        held = jnp.zeros((row_fetch.cold_rows(ids.shape[1], cap, tile, BLOCK), LANES),
                         jnp.float32)
        rows = []
        for c in range(ids.shape[0]):
            held = row_fetch.fetch_cold(table, cold, int(first[c]),
                                        int(local.lengths[c]) // BLOCK, held, BLOCK)
            rows.append(np.asarray(row_fetch.fetch(
                jnp.asarray(loc[c]), jnp.asarray(local.starts[c]), hot, held, cap=cap,
                tile=tile, unroll=unroll, interpret=True)))
    return np.stack(rows), local


@pytest.mark.parametrize("name", CASES)
def test_the_fetched_rows_are_the_gathers_to_the_bit(name):
    rng = np.random.default_rng(CASES.index(name))
    table = rng.standard_normal((ROWS, LANES)).astype(np.float32)
    table[-1] = 0
    ids, hot_ids = _case(name, rng)
    got, local = _fetch(table, ids, hot_ids)
    np.testing.assert_array_equal(got, table[ids])
    cold = ~np.isin(ids, hot_ids)
    assert local.cold_slots == cold.sum()
    # every run starts and ends on a group of eight, a call's list is whole blocks
    assert not (local.starts % row_fetch.GROUP).any() and local.run % row_fetch.GROUP == 0
    assert not (local.lengths % BLOCK).any() and local.cold.size == local.lengths.sum()
    assert local.run <= TILE
    # the padding names rows one after another, not one row again and again
    assert all(np.bincount(block).max() <= max(2, (cold & np.isin(ids, block)).sum())
               for block in local.cold.reshape(-1, BLOCK))
    if name == "no hot slot":
        assert local.cold_slots == ids.size
    if name == "every slot hot":
        assert local.cold_slots == 0 and local.cold.size == 0
    if name == "a tile of cold slots alone":
        assert local.run == TILE


@pytest.mark.parametrize("unroll", [None, 8, 16, 32])
def test_however_many_slots_are_unrolled_the_same_rows_are_fetched(unroll):
    rng = np.random.default_rng(20)
    table = rng.standard_normal((ROWS, LANES)).astype(np.float32)
    ids, hot_ids = _case("a call that ends inside a tile", rng)
    ids = ids[:, :2 * TILE + 256]                                 # whole groups of 32
    got, _ = _fetch(table, ids, hot_ids, unroll=unroll)
    np.testing.assert_array_equal(got, table[ids])


def test_a_call_of_no_whole_groups_is_refused():
    with pytest.raises(ValueError, match="whole groups"):
        row_fetch.fetch(jnp.zeros(12, jnp.int32), jnp.zeros(1, jnp.int32),
                        jnp.zeros((8, LANES)), jnp.zeros((8, LANES)), cap=8,
                        interpret=True)


@pytest.mark.parametrize("rows,hot", [(1, 8), (9, 16), (600, 1024), (65_536, 65_536),
                                      (65_537, 65_536), (1_000_991, 65_536)])
def test_the_hot_rows_are_the_power_of_two_that_holds_the_table(rows, hot):
    assert row_fetch.hot_rows(rows) == hot


@pytest.mark.parametrize("case,dtype,lanes,share,word", [
    ("a CPU", np.float32, 128, 0.9, "not a TPU"),
    ("bfloat16 rows", jnp.bfloat16, 128, 0.9, "bfloat16"),
    ("float64 rows", np.float64, 128, 0.9, "float64"),
    ("rows of 100 floats", np.float32, 100, 0.9, "100 floats"),
    ("flat degrees", np.float32, 128, 0.2, "cover 0.200"),
    ("just under the threshold", np.float32, 128, row_fetch.MIN_HOT_SHARE - 1e-3, "cover"),
    ("the cell's users' half-step", np.float32, 128, 0.88, None),
    ("just over the threshold", np.float32, 128, row_fetch.MIN_HOT_SHARE + 1e-3, None),
])
def test_where_the_kernel_is_taken(monkeypatch, case, dtype, lanes, share, word):
    """The backend, the dtype, the lanes and the hot rows' share of the
    slots: nothing else is read."""
    monkeypatch.delenv(_mosaic.ENV_INTERPRET_VAR, raising=False)
    if case != "a CPU":
        monkeypatch.setattr(_mosaic, "interpret_mode", lambda: False)
    reason = row_fetch.unsupported_reason(dtype, lanes, share)
    if word is None:
        assert reason is None
    else:
        assert word in reason


def test_the_threshold_is_the_probes_two_readings():
    """``(1 - cold) * 9.5 > t_k``, and a tenth of the slots for what a
    call and the runs' padding cost."""
    assert row_fetch.MIN_HOT_SHARE == pytest.approx(
        row_fetch.KERNEL_NS_A_SLOT / row_fetch.GATHER_NS_A_ROW + 0.1)
    assert 0.3 < row_fetch.MIN_HOT_SHARE < 0.6
