"""Pass 3 (transfer/retrace guard) tests + the satellite regressions that
ride on the fused executor: the constant weak_type cache-key fix and the
LazyDeviceColumn donated-buffer error paths.
"""

import numpy as np
import pytest

from flinkml_tpu import pipeline_fusion
from flinkml_tpu.analysis import GuardViolation, TransferRetraceGuard
from flinkml_tpu.api import ColumnKernel
from flinkml_tpu.models.scalers import MaxAbsScaler, StandardScaler
from flinkml_tpu.pipeline import PipelineModel
from flinkml_tpu.table import LazyDeviceColumn, Table


@pytest.fixture(autouse=True)
def _fusion_state():
    pipeline_fusion.set_enabled(True)
    pipeline_fusion.reset_cache()
    saved = list(pipeline_fusion.on_compile)
    yield
    pipeline_fusion.on_compile[:] = saved
    pipeline_fusion.set_enabled(True)
    pipeline_fusion.reset_cache()


def _data(n=60, d=4, seed=0):
    rng = np.random.default_rng(seed)
    return Table({"features": rng.normal(size=(n, d))})


def _two_stage_chain(t):
    a = StandardScaler().set(StandardScaler.INPUT_COL, "features").set(
        StandardScaler.OUTPUT_COL, "a"
    ).fit(t)
    b = MaxAbsScaler().set(MaxAbsScaler.INPUT_COL, "a").set(
        MaxAbsScaler.OUTPUT_COL, "b"
    ).fit(a.transform(t)[0])
    return PipelineModel([a, b])


# ---------------------------------------------------------------------------
# guard semantics
# ---------------------------------------------------------------------------

def test_warm_hot_loop_passes_with_zero_budget():
    t = _data()
    pm = _two_stage_chain(t)
    pm.transform(t)  # warmup compile outside the guard
    with TransferRetraceGuard(allow_compiles=0):
        for rows in (60, 33, 47, 64):  # one 64-row bucket
            pm.transform(t.slice(0, rows))


def test_new_chain_compile_inside_guard_violates():
    t = _data()
    pm = _two_stage_chain(t)
    with pytest.raises(GuardViolation) as err:
        with TransferRetraceGuard(allow_compiles=0):
            pm.transform(t)  # cold chain: compiles in-region
    assert any(f.rule == "FML402" for f in err.value.findings)
    # The same loop with a declared budget passes.
    pipeline_fusion.reset_cache()
    with TransferRetraceGuard(allow_compiles=1):
        pm.transform(t)


@pytest.fixture(params=["none", "memory"])
def compile_store(request):
    """Both states a process can be in: no compile-cache store, and the
    memory-only store every ``ReplicaPool`` leaves active (the fused
    cache's keys then carry the placement too: under the driver's six
    workers this file follows files that started pools)."""
    from flinkml_tpu import compile_cache

    compile_cache.reset()
    if request.param == "memory":
        compile_cache.ensure_store()
    yield
    compile_cache.reset()


def test_new_bucket_compile_is_policy_allowed(compile_store):
    t = _data(n=200)
    pm = _two_stage_chain(t)
    pm.transform(t.slice(0, 60))  # warm the 64 bucket
    with TransferRetraceGuard(allow_compiles=0, allow_new_buckets=True):
        pm.transform(t.slice(0, 129))  # 256 bucket: allowed
    pipeline_fusion.reset_cache()
    pm.transform(t.slice(0, 60))
    with pytest.raises(GuardViolation):
        with TransferRetraceGuard(allow_compiles=0,
                                  allow_new_buckets=False):
            pm.transform(t.slice(0, 129))


def test_transfer_budgets_fml401():
    t = _data()
    pm = _two_stage_chain(t)
    pm.transform(t)
    guard = TransferRetraceGuard(
        allow_compiles=0, allow_host_to_device=0,
        raise_on_violation=False,
    )
    with guard:
        fresh = _data(seed=1)  # a NEW table: its upload is "implicit"
        pm.transform(fresh)
    assert [f.rule for f in guard.findings] == ["FML401"]

    # Device->host reads inside the region are caught too.
    (out,) = pm.transform(t)
    guard2 = TransferRetraceGuard(
        allow_compiles=0, allow_device_to_host=0, raise_on_violation=False,
    )
    with guard2:
        out.column("b")
    assert [f.rule for f in guard2.findings] == ["FML401"]


def test_guard_reports_not_raises_when_asked():
    t = _data()
    pm = _two_stage_chain(t)
    guard = TransferRetraceGuard(allow_compiles=0, raise_on_violation=False)
    with guard:
        pm.transform(t)
    assert guard.findings and guard.findings[0].rule == "FML402"


@pytest.mark.no_retrace(allow_compiles=1)
def test_no_retrace_marker_budgets_warmup():
    """The pytest marker wraps the test in the guard: one compile for the
    cold chain is budgeted, the following varied-size calls must all hit
    the cache (a retrace here fails this test via GuardViolation)."""
    t = _data()
    pm = _two_stage_chain(t)
    for rows in (60, 33, 47):
        pm.transform(t.slice(0, rows))


def _fp_chain(fp_suffix):
    """A chain identical in everything but its fingerprint — the shape an
    unstable fingerprint produces on every call."""
    def f1(cols, c, valid):
        return {"y": cols["x"] * 2.0}

    def f2(cols, c, valid):
        return {"z": cols["y"] + 0}

    return [
        ColumnKernel(("x",), ("y",), f1, fingerprint=("mul", fp_suffix)),
        ColumnKernel(("y",), ("z",), f2, fingerprint=("id",)),
    ]


def test_fingerprint_churn_flagged_fml403_but_pair_is_not():
    t = Table({"x": np.ones(10)})
    # Two distinct chains (an A/B pair) with the same shapes: budgeted,
    # NOT churn.
    guard = TransferRetraceGuard(allow_compiles=2, raise_on_violation=False)
    with guard:
        pipeline_fusion.execute_kernel_chain(t, _fp_chain(0))
        pipeline_fusion.execute_kernel_chain(t, _fp_chain(1))
    assert not guard.findings, [f.rule for f in guard.findings]
    # Three+ fingerprints over identical specs = churn.
    pipeline_fusion.reset_cache()
    guard = TransferRetraceGuard(allow_compiles=3, raise_on_violation=False)
    with guard:
        for i in range(3):
            pipeline_fusion.execute_kernel_chain(t, _fp_chain(i))
    assert "FML403" in [f.rule for f in guard.findings]


# ---------------------------------------------------------------------------
# satellite: constant weak_type in the compile-cache key
# ---------------------------------------------------------------------------

def _mul_chain(const):
    """Two-kernel chain whose first kernel multiplies by a constant; a
    python-float constant is weak float64, an np scalar is strong."""
    def mul(cols, c, valid):
        return {"y": cols["x"] * c["k"]}

    def ident(cols, c, valid):
        return {"z": cols["y"] + 0}

    return [
        ColumnKernel(("x",), ("y",), mul, {"k": const}, ("mul",)),
        ColumnKernel(("y",), ("z",), ident, fingerprint=("ident",)),
    ]


def test_constant_weak_type_does_not_alias_cached_program():
    """Regression: the cache key once recorded only (dtype, shape) of each
    constant. A weak-float64 constant (python scalar) and a strong-float64
    constant then aliased one executable even though they trace to
    DIFFERENT programs over float32 columns (weak * f32 -> f32,
    strong * f32 -> f64) — the second caller silently got the first
    caller's dtypes. The key now includes weak_type."""
    t = Table({"x": np.ones(10, dtype=np.float32)})
    weak = pipeline_fusion.execute_kernel_chain(t, _mul_chain(2.0))
    strong = pipeline_fusion.execute_kernel_chain(
        t, _mul_chain(np.float64(2.0))
    )
    assert weak.column("z").dtype == np.float32
    assert strong.column("z").dtype == np.float64
    assert pipeline_fusion.compiled_program_count() == 2
    np.testing.assert_array_equal(weak.column("z"), 2.0 * np.ones(10))
    np.testing.assert_array_equal(strong.column("z"), 2.0 * np.ones(10))


# ---------------------------------------------------------------------------
# satellite: LazyDeviceColumn error paths
# ---------------------------------------------------------------------------

def test_lazy_column_clear_error_after_source_buffer_freed():
    """Reading a lazy intermediate after its captured source buffers were
    donated/freed raises a clear, named error — not a jax internal error
    or stale data — and stays a clear error on repeated reads."""
    t = _data(n=20)
    pm = _two_stage_chain(t)
    (out,) = pm.transform(t)
    assert isinstance(out._columns["a"], LazyDeviceColumn)
    for buf in list(t._device_cache.values()):
        buf.delete()
    with pytest.raises(RuntimeError, match="donated or freed"):
        out.column("a")
    with pytest.raises(RuntimeError, match="lazy intermediate column 'a'"):
        out.column("a")


def test_lazy_column_clear_error_when_own_buffer_freed():
    """A lazy column materialized once and then freed must also fail
    loudly on the next device-side use, not crash or serve stale bits."""
    t = _data(n=20)
    pm = _two_stage_chain(t)
    (out,) = pm.transform(t)
    col = out._columns["a"]
    _ = col.buf  # materialize the device buffer
    col.buf.delete()
    with pytest.raises(RuntimeError, match="donated or freed"):
        _ = col.buf


def test_lazy_column_reads_before_free_still_work():
    t = _data(n=20)
    pm = _two_stage_chain(t)
    pipeline_fusion.set_enabled(False)
    (expected,) = pm.transform(t)
    pipeline_fusion.set_enabled(True)
    (out,) = pm.transform(t)
    np.testing.assert_array_equal(out.column("a"), expected.column("a"))
    # Host cache survives a later free: the column was already fetched.
    for buf in list(t._device_cache.values()):
        buf.delete()
    np.testing.assert_array_equal(out.column("a"), expected.column("a"))
