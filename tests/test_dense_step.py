"""The dense linear step that reads its window once (PR 40):
``kernels.dense_step.margin_grad``, a TPU's one Mosaic kernel, here
interpreted, held to the lines of ``_linear_sgd.make_dense_step`` it
stands in for.

- the window's gradient, loss and weight sum are XLA's (``xb @ coef``,
  ``margin_terms``, ``xb.T @ mult``) to float32 rounding and a float64
  replay's to a few ulps of the sums, three losses, ``dim`` 123 and 256,
  the first, a middle and the last window of a table (the start is a
  traced scalar: one program), weights not all one; and the same bits on
  a second run;
- where the kernel applies is read off the backend and the step's
  operands (``unsupported_reason``), nothing sets it; where it does not
  the step is the five lines it was;
- a whole fit through the interpreted kernel is the plain fit, on one
  device and on two; the fit counts ``trainer.fused_dense_fits``.
"""

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flinkml_tpu.kernels import _mosaic, dense_step
from flinkml_tpu.models import _linear_sgd
from flinkml_tpu.ops.losses import margin_terms
from flinkml_tpu.parallel import DeviceMesh
from flinkml_tpu.utils.metrics import metrics

LOSSES = ["logistic", "hinge", "squared"]
ROWS, BS = 8192, 2048


@functools.lru_cache(maxsize=None)
def _table(dim, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((ROWS, dim)).astype(np.float32)
    coef = (0.3 * rng.standard_normal(dim)).astype(np.float32)
    y = (x @ coef + rng.standard_normal(ROWS) > 0).astype(np.float32)
    w = (0.25 + rng.random(ROWS)).astype(np.float32)
    return x, y, w, coef


@functools.lru_cache(maxsize=None)
def _kernel(loss):
    return jax.jit(functools.partial(
        dense_step.margin_grad, loss, local_bs=BS, interpret=True))


@functools.lru_cache(maxsize=None)
def _xla(loss):
    """The lines of ``make_dense_step`` the kernel stands in for."""

    def sums(xl, yl, wl, coef, epoch):
        xb, yb, wb = (_linear_sgd._window(a, epoch, BS) for a in (xl, yl, wl))
        mult, per_ex = margin_terms(loss, xb @ coef, yb, wb)
        return xb.T @ mult, jnp.sum(per_ex), jnp.sum(wb)

    return jax.jit(sums)


def _replay(loss, x, y, w, coef, window):
    """The same sums in NumPy float64."""
    rows = slice(window * BS, (window + 1) * BS)
    xb, yb, wb = (a[rows].astype(np.float64) for a in (x, y, w))
    dot, ys = xb @ coef.astype(np.float64), 2.0 * yb - 1.0
    if loss == "logistic":
        mult = -wb * ys / (1.0 + np.exp(dot * ys))
        per_ex = wb * np.logaddexp(0.0, -dot * ys)
    elif loss == "hinge":
        mult = -wb * ys * (dot * ys < 1.0)
        per_ex = wb * np.maximum(0.0, 1.0 - dot * ys)
    else:
        mult, per_ex = wb * (dot - yb), 0.5 * wb * (dot - yb) ** 2
    return xb.T @ mult, per_ex.sum(), wb.sum(), np.abs(xb * mult[:, None]).sum(axis=0)


@pytest.mark.parametrize("window", [0, 2, 3])
@pytest.mark.parametrize("dim", [123, 256])
@pytest.mark.parametrize("loss", LOSSES)
def test_a_windows_sums_are_xlas_and_a_float64_replays(loss, dim, window):
    x, y, w, coef = _table(dim)
    start = jnp.int32(window * BS)
    grad, loss_sum, wsum = _kernel(loss)(x, y, w, coef, start)
    assert grad.shape == (dim,) and grad.dtype == jnp.float32
    want = _xla(loss)(x, y, w, coef, jnp.int32(window + 4))  # wraps: window mod 4
    exact_grad, exact_loss, exact_wsum, mass = _replay(loss, x, y, w, coef, window)
    # A float32 sum of 2,048 terms, chunk after chunk: a few ulps of the
    # terms' mass (XLA's own are as far or further).
    ulp = np.finfo(np.float32).eps
    np.testing.assert_array_less(np.abs(np.asarray(grad) - exact_grad), 8 * ulp * mass)
    np.testing.assert_allclose(grad, want[0], rtol=0, atol=16 * ulp * mass.max())
    np.testing.assert_allclose(float(loss_sum), exact_loss, rtol=8 * ulp)
    np.testing.assert_allclose(float(wsum), exact_wsum, rtol=8 * ulp)
    np.testing.assert_allclose(float(loss_sum), float(want[1]), rtol=16 * ulp)
    # the same bits on a second run: one fixed order, no atomics
    again = _kernel(loss)(x, y, w, coef, start)
    assert np.asarray(grad).tobytes() == np.asarray(again[0]).tobytes()
    assert float(loss_sum) == float(again[1]) and float(wsum) == float(again[2])


def test_the_windows_start_is_an_operand_and_other_windows_are_not_read():
    """One traced program for every window (the start goes to SMEM
    before the grid runs), and rows outside the window change nothing."""
    x, y, w, coef = _table(123)
    f = _kernel("logistic")
    got = f(x, y, w, coef, jnp.int32(BS))
    traced = f._cache_size()
    f(x, y, w, coef, jnp.int32(3 * BS))
    other = x.copy()
    other[:BS] = 7.0
    other[2 * BS:] = np.nan
    same = f(other, y, w, coef, jnp.int32(BS))
    assert np.asarray(got[0]).tobytes() == np.asarray(same[0]).tobytes()
    assert f._cache_size() == traced


def test_a_tile_shorter_than_the_window_gives_the_sums_of_the_whole(monkeypatch):
    """The grid's axis is sequential and the sums stay over it: eight
    tiles of 1,024 rows (eight chunks split at a time) and two of 4,096
    (sixteen) sum the same rows, in another grouping of the chunks."""
    x, y, w, coef = _table(123)
    wide = jax.jit(functools.partial(
        dense_step.margin_grad, "squared", local_bs=ROWS, interpret=True))(
            x, y, w, coef, jnp.int32(0))
    monkeypatch.setattr(dense_step, "TILE", 1024)
    assert dense_step.tile_rows(ROWS, 123) == 1024
    short = jax.jit(functools.partial(
        dense_step.margin_grad, "squared", local_bs=ROWS, interpret=True))(
            x, y, w, coef, jnp.int32(0))
    mass = _replay("squared", x, y, w, coef, 0)[3].max() * (ROWS // BS)
    np.testing.assert_allclose(wide[0], short[0], rtol=0,
                               atol=16 * np.finfo(np.float32).eps * mass)
    np.testing.assert_allclose(float(wide[1]), float(short[1]), rtol=1e-6)
    np.testing.assert_allclose(float(wide[2]), float(short[2]), rtol=1e-6)


def test_the_kernel_traces_in_32_bit_mode_under_x64():
    """PR 30's lesson: one float64 block and Mosaic aborts the process."""
    x, y, w, coef = _table(123)
    with jax.enable_x64(True):
        program = jax.make_jaxpr(functools.partial(
            dense_step.margin_grad, "logistic", local_bs=BS, interpret=True))(
                x, y, w, coef, jnp.int32(0))
    (call,) = [e for e in program.jaxpr.eqns if e.primitive.name == "pallas_call"]
    inner = call.params["jaxpr"]
    assert not [str(v.aval) for v in inner.invars + inner.outvars
                if "64" in str(v.aval.dtype)]


@pytest.mark.parametrize("order", list(itertools.permutations(range(3))))
def test_a_floats_three_parts_sum_to_it_in_any_order(order):
    """What the MXU is handed: three bfloat16s, disjoint bit fields of
    the float32's significand, so that however its float32 accumulation
    takes them along the contraction no partial sum is rounded."""
    rng = np.random.default_rng(11)
    v = rng.standard_normal(8192) * np.exp(rng.uniform(-60.0, 60.0, 8192))
    v = np.concatenate([v, [0.0, -0.0, 1.0, -1.0, 3.0e38, 1.0 + 2.0 ** -23]])
    v = v.astype(np.float32)
    parts = [np.asarray(p) for p in jax.jit(dense_step.disjoint_parts)(v)]
    for part in parts:
        assert part.dtype == np.float32
        np.testing.assert_array_equal(
            part, np.asarray(jnp.asarray(part).astype(jnp.bfloat16).astype(jnp.float32)))
    first, second, third = (parts[i] for i in order)
    np.testing.assert_array_equal((first + second) + third, v)
    assert np.abs(parts[1]).max() > 0 and np.abs(parts[2]).max() > 0


def test_the_rows_split_at_a_time_get_fewer_as_they_get_wider():
    assert [dense_step.chunks(d) for d in (8, 123, 128, 250, 384, 512, 1020, 2048)] \
        == [16, 16, 16, 8, 4, 4, 2, 1]
    # a tile is whole runs of them
    assert dense_step.chunks(123, 1024) == 8 and dense_step.chunks(640, 1024) == 2


REFUSALS = {
    "float32 whole windows of whole tiles": (jnp.float32, 9_437_184, 262_144, 123, None),
    "a multiple of 128 features": (jnp.float32, 65_536, 8_192, 256, None),
    "the widest": (jnp.float32, 65_536, 1_024, 2_048, None),
    "bfloat16-resident rows": (jnp.bfloat16, 65_536, 8_192, 123, "bfloat16"),
    "float64 rows": (jnp.float64, 65_536, 8_192, 123, "float64"),
    "a ragged shard": (jnp.float32, 65_536 + 1_024, 8_192, 123, "whole windows"),
    "a window off the tile": (jnp.float32, 60_000, 1_000, 123, "whole tiles"),
    "a window of one short tile": (jnp.float32, 5_120, 512, 123, "whole tiles"),
    "features over the limit": (jnp.float32, 65_536, 8_192, 2_049, "2049 features"),
    "rows the chip lays along the lanes": (jnp.float32, 65_536, 8_192, 784, "rows"),
    "a last row of lanes half empty": (jnp.float32, 65_536, 8_192, 200, "rows"),
}


@pytest.mark.parametrize("case", list(REFUSALS))
def test_where_the_kernel_applies_is_read_off_the_step(case, monkeypatch):
    dtype, n_local, local_bs, dim, why = REFUSALS[case]
    # here, on a CPU, Mosaic's kernel would be interpreted: XLA's products
    assert "not a TPU" in dense_step.unsupported_reason(dtype, n_local, local_bs, dim)
    monkeypatch.setattr(_mosaic, "interpret_mode", lambda: False)     # a TPU
    reason = dense_step.unsupported_reason(dtype, n_local, local_bs, dim)
    assert (reason is None) if why is None else (why in reason)
    xl = jax.ShapeDtypeStruct((n_local, dim), dtype)
    coef = jax.ShapeDtypeStruct((dim,), dtype)
    assert _linear_sgd._rows_in_fast_memory(xl, coef, local_bs) == (why is None)


def test_a_tile_is_the_longest_that_divides_the_window_and_fits():
    assert dense_step.tile_rows(262_144, 123) == dense_step.TILE == 4096
    assert dense_step.tile_rows(3 * 2_048, 123) == 2_048
    assert dense_step.tile_rows(1_024, 2_048) == 1_024       # 8 MiB a buffer
    assert dense_step.tile_rows(4_096, 2_048) == 1_024
    assert dense_step.tile_rows(1_000, 123) is None


def _step_program(dtype, n_local=ROWS, dim=123, bs=BS):
    step = _linear_sgd.make_dense_step("logistic", bs, "data")
    args = [jax.ShapeDtypeStruct((dim,), dtype), jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((n_local, dim), dtype),
            jax.ShapeDtypeStruct((n_local,), dtype),
            jax.ShapeDtypeStruct((n_local,), dtype)]
    args += [jax.ShapeDtypeStruct((), dtype)] * 3
    return jax.make_jaxpr(lambda *a: step(*a), axis_env=[("data", 1)])(*args)


#: ``make_dense_step``'s body before PR 40, to the letter: what a step
#: the kernel does not take has to lower to.
def _step_before(loss, local_bs, axis):
    _window, _acc_dt = _linear_sgd._window, _linear_sgd._acc_dt
    _margin_grad, _soft_threshold = margin_terms, _linear_sgd._soft_threshold

    def step(coef, epoch, xl, yl, wl, learning_rate, reg_l2, reg_l1):
        xb = _window(xl, epoch, local_bs)
        yb = _window(yl, epoch, local_bs)
        wb = _window(wl, epoch, local_bs)
        acc = _acc_dt(xb.dtype)
        dot = xb @ coef
        mult, per_ex = _margin_grad(loss, dot, yb, wb)
        grad_l = xb.T @ mult
        loss_l = jnp.sum(per_ex.astype(acc))
        wsum_l = jnp.sum(wb.astype(acc))
        grad = jax.lax.psum(grad_l, axis)
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


@pytest.mark.parametrize("case", ["a CPU", "bfloat16 rows", "a ragged shard",
                                  "features over the limit"])
def test_a_step_the_kernel_does_not_take_is_the_step_as_it_was(case, monkeypatch):
    dtype, n_local, dim = jnp.float32, ROWS, 123
    if case != "a CPU":
        monkeypatch.setattr(_mosaic, "interpret_mode", lambda: False)
    if case == "bfloat16 rows":
        dtype = jnp.bfloat16
    elif case == "a ragged shard":
        n_local = ROWS + 1024
    elif case == "features over the limit":
        dim = 2176
    program = _step_program(dtype, n_local, dim)
    assert "pallas_call" not in str(program)
    before = _step_before("logistic", BS, "data")
    args = [v.aval for v in program.jaxpr.invars]
    want = jax.make_jaxpr(lambda *a: before(*a), axis_env=[("data", 1)])(*args)
    assert str(program) == str(want)


def test_on_a_tpu_the_step_is_one_kernel_and_no_slice_of_the_rows(monkeypatch):
    monkeypatch.setattr(_mosaic, "interpret_mode", lambda: False)
    outside = [e.primitive.name for e in _step_program(jnp.float32).jaxpr.eqns]
    assert outside.count("pallas_call") == 1
    # the kernel's own products are of a chunk in fast memory; none of
    # XLA's, and no slice, over the rows
    assert "dynamic_slice" not in outside and "dot_general" not in outside


def _fit(mesh, x, y, w, loss="logistic", **kw):
    _linear_sgd._dense_trainer.cache_clear()      # keyed by no backend
    counters = metrics.group("trainer")
    before = counters.snapshot()["counters"].get("fused_dense_fits", 0.0)
    coef = _linear_sgd.train_linear_model(
        x, y, w, loss, mesh, 8, 0.5, BS, 0.01, 0.5, 0.0, 3, dtype=np.float32, **kw)
    return coef, counters.snapshot()["counters"]["fused_dense_fits"] - before


@pytest.mark.parametrize("devices", [1, 2])
@pytest.mark.parametrize("loss", LOSSES)
def test_a_fit_through_the_kernel_is_the_plain_fit(loss, devices, monkeypatch):
    """The whole trainer (``_dense_trainer``'s loop, the ``psum``s, the
    prox step) with the kernel interpreted in place of XLA's products,
    against the fit as a CPU runs it; ``trainer.fused_dense_fits`` says
    which ran. (An interpreted kernel's values carry no mesh axes, so the
    trainer's ``shard_map`` does not check them here.)"""
    x, y, w, _ = _table(123, seed=5)
    mesh = DeviceMesh(devices=jax.devices()[:devices])
    plain, counted = _fit(mesh, x, y, w, loss)
    assert counted == 0.0
    monkeypatch.setattr(dense_step, "unsupported_reason", lambda *a: None)
    monkeypatch.setattr(jax, "shard_map",
                        functools.partial(jax.shard_map, check_vma=False))
    fused, counted = _fit(mesh, x, y, w, loss)
    _linear_sgd._dense_trainer.cache_clear()
    assert counted == 1.0
    np.testing.assert_allclose(fused, plain, rtol=0, atol=1e-6)
    assert np.abs(plain).max() > 1e-2


def test_the_fused_share_reads_the_count_over_the_fits():
    """``benchmark/metrics/trainer.dense_fused_step_share.json`` through
    the benchmark's ``counter_ratio`` over a window's counters as
    ``benchmark/run.py`` flattens them, and its entry in
    ``BENCHMARK.json``."""
    import json
    import os

    from benchmark.readers import counter_ratio

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    name = "trainer.dense_fused_step_share"
    with open(os.path.join(root, "benchmark", "metrics", f"{name}.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "counter_ratio"
    assert spec["params"] == {"num": "trainer.fused_dense_fits", "den": "fits"}
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": "fits/fit", "better": "higher",
        "source": "program_counter", "layer": "Kernels",
        "moves": "fit_samples_per_s", "workloads": ["lr-a9a.fit"]}
    obs = {"setup_counters": {}, "units": {"fits": 245}}
    for fused, share in ((245.0, 1.0), (0.0, 0.0)):
        counters = {"trainer.fused_dense_fits": fused, "trainer.steps": 17640.0}
        assert counter_ratio.read(spec["params"], {**obs, "counters": counters}) == share
    # a program without the count (the parent): no metric, no error
    assert counter_ratio.read(
        spec["params"], {**obs, "counters": {"trainer.steps": 17640.0}}) is None
