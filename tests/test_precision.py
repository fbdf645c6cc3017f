"""Policy-gated mixed precision (ISSUE 10): the PrecisionPolicy value,
the FML6xx precision-flow pass (pass 5), and the three gated paths —
the fused transform executor, the plan-sharded SGD/Adam trainers, and
serving.

Covers: the policy value itself (presets, JSON round-trip, hashability,
resolution), FML601-605 each on a seeded fixture AND FML601/602/603 on
REAL in-repo jaxprs (the linear trainer step, the fused kernel chains),
typed pre-compile refusals carrying the findings, pinned-numerics /
convergence-tolerance equivalence vs the f32 baselines for every gated
path, bf16/f32 compile-cache non-aliasing (the would-have-aliased
regression), the shared FML106 dtype-flow path, and the CLI's
``--format json`` output.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from flinkml_tpu import pipeline_fusion
from flinkml_tpu.analysis.precision import (
    check_policy_file,
    check_policy_plan,
    check_precision_fn,
    promotion_findings,
    validate_precision,
)
from flinkml_tpu.api import ColumnKernel
from flinkml_tpu.parallel import DeviceMesh
from flinkml_tpu.pipeline import PipelineModel
from flinkml_tpu.precision import (
    FULL,
    INT8_INFERENCE,
    MIXED,
    MIXED_INFERENCE,
    PrecisionPolicy,
    PrecisionValidationError,
    cast_floats,
    dequantize_absmax,
    is_narrower,
    quantize_absmax,
    resolve_policy,
)
from flinkml_tpu.serving.engine import ServingConfig, ServingEngine
from flinkml_tpu.serving.registry import ModelRegistry
from flinkml_tpu.sharding.apply import (
    linear_step_fn,
    train_linear_plan,
    validate_linear_precision,
)
from flinkml_tpu.sharding.plan import FSDP, REPLICATED
from flinkml_tpu.table import Table

FIXDIR = os.path.join(os.path.dirname(__file__), "analysis_fixtures")
BF16 = np.dtype(ml_dtypes.bfloat16)


# ---------------------------------------------------------------------------
# The policy value
# ---------------------------------------------------------------------------


def test_policy_presets_and_roundtrip():
    assert MIXED.compute == "bfloat16"
    assert MIXED.accum == MIXED.params == "float32"
    assert MIXED.mixed and not FULL.mixed
    assert not MIXED_INFERENCE.mixed or MIXED_INFERENCE.mixed  # defined
    again = PrecisionPolicy.from_json_dict(
        json.loads(json.dumps(MIXED.to_json_dict()))
    )
    assert again == MIXED
    assert hash(again) == hash(MIXED)  # compile-cache key material


def test_policy_accum_narrower_than_compute_refused():
    with pytest.raises(ValueError, match="accum"):
        PrecisionPolicy("bad", compute="float32", accum="bfloat16")


def test_policy_resolution_forms():
    assert resolve_policy(None) is None
    assert resolve_policy("mixed") is MIXED
    assert resolve_policy(MIXED) is MIXED
    assert resolve_policy(MIXED.to_json_dict()) == MIXED
    with pytest.raises(ValueError, match="preset"):
        resolve_policy("bf16-ish")
    with pytest.raises(TypeError):
        resolve_policy(3.14)


def test_narrowness_is_significand_ranked():
    # bf16 (8-bit significand) is NARROWER than f16 (11) despite equal
    # itemsize — accumulation correctness is a rounding question.
    assert is_narrower("bfloat16", "float16")
    assert is_narrower("float16", "float32")
    assert not is_narrower("float32", "float32")
    assert not is_narrower("int32", "float32")  # non-floats never narrow


def test_cast_floats_is_the_to_bf16_idiom():
    tree = {"coef": np.ones(3, np.float32), "step": np.int32(7)}
    down = cast_floats(tree, BF16)
    assert down["coef"].dtype == BF16
    assert down["step"].dtype == np.int32  # non-floats pass through


# ---------------------------------------------------------------------------
# The FML6xx pass on REAL in-repo jaxprs
# ---------------------------------------------------------------------------


def _sgd_step(dtype, policy=None):
    return linear_step_fn("logistic", "sgd", np.dtype(dtype).name,
                          0.1, 0.9, 0.0, 0.0, policy=policy)


def test_fml601_603_real_trainer_step_refused():
    """A deliberately mis-cast trainer step (bf16 STORAGE under the
    mixed policy) is refused pre-compile with both rules, typed."""
    with pytest.raises(PrecisionValidationError) as ei:
        validate_linear_precision(
            MIXED, _sgd_step(BF16), dim=8, rows=8, dt=BF16,
            optimizer="sgd",
        )
    rules = {f.rule for f in ei.value.findings}
    assert "FML601" in rules and "FML603" in rules
    # The typed error CARRIES the structured findings (CI annotates).
    assert all(f.severity == "error" for f in ei.value.findings)


def test_policy_correct_step_validates_clean():
    validate_linear_precision(
        MIXED, _sgd_step(np.float32, policy=MIXED), dim=8, rows=8,
        dt=np.float32, optimizer="sgd",
    )
    validate_linear_precision(
        MIXED, linear_step_fn("logistic", "adam", "float32", 0.1, 0.9,
                              0.0, 0.0, policy=MIXED),
        dim=8, rows=8, dt=np.float32, optimizer="adam",
    )


def test_fml602_stray_wide_constant_real_jaxpr():
    const = np.float32(1.5)  # STRONG f32 constant in a bf16 region

    def chain(x):
        return (x.astype(BF16) * 2.0) * const

    findings = check_precision_fn(
        chain, jax.ShapeDtypeStruct((8, 4), np.float32),
        policy=MIXED_INFERENCE,
    )
    assert {f.rule for f in findings} == {"FML602"}
    assert "promotes" in findings[0].message


def test_fml602_weak_constant_is_fine():
    def chain(x):
        return (x.astype(BF16) * 2.0) * 1.5  # python scalar: weak

    assert check_precision_fn(
        chain, jax.ShapeDtypeStruct((8, 4), np.float32),
        policy=MIXED_INFERENCE,
    ) == []


def test_fml604_narrow_collective_and_sanctioned_precast():
    def bad(g):
        return jax.lax.psum(g, "data")

    findings = check_precision_fn(
        bad, jax.ShapeDtypeStruct((8,), BF16), policy=MIXED,
        axis_env=[("data", 8)],
    )
    assert {f.rule for f in findings} == {"FML604"}

    def deliberate(g):
        # Explicit narrowing cast right before the collective declares
        # the bandwidth-for-precision trade — allowed.
        return jax.lax.psum(g.astype(BF16), "data")

    assert check_precision_fn(
        deliberate, jax.ShapeDtypeStruct((8,), np.float32), policy=MIXED,
        axis_env=[("data", 8)],
    ) == []


def test_fml605_plan_width_conflict():
    assert check_policy_plan(MIXED, dtype_bytes=2, plan_name="fsdp")[0] \
        .rule == "FML605"
    assert check_policy_plan(MIXED, dtype_bytes=4) == []
    assert check_policy_plan(MIXED, dtype_bytes=None) == []


def test_scan_carry_provenance_recurses():
    """A scan whose CARRY updates at bf16 is state math running narrow —
    the walker must tag carries through the scan body (FML601)."""
    def loop(x):
        def body(carry, t):
            return carry + t, ()

        out, _ = jax.lax.scan(
            body, x.astype(BF16), jnp.zeros((4,) + x.shape, BF16)
        )
        return out

    findings = check_precision_fn(
        loop, jax.ShapeDtypeStruct((8,), np.float32), policy=MIXED,
    )
    assert "FML601" in {f.rule for f in findings}


# ---------------------------------------------------------------------------
# Seeded fixtures + CLI
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,rule", [
    ("bad_precision_fml601_bf16_accum_sgd.policy.json", "FML601"),
    ("bad_precision_fml602_stray_constant.policy.json", "FML602"),
    ("bad_precision_fml603_bf16_master_weights.policy.json", "FML603"),
    ("bad_precision_fml604_bf16_psum.policy.json", "FML604"),
    ("bad_precision_fml605_plan_width_conflict.policy.json", "FML605"),
    ("bad_precision_fml606_int8_unscaled_accum.policy.json", "FML606"),
    ("bad_precision_fml607_int8_republished_full.policy.json", "FML607"),
])
def test_seeded_fixture_flagged(name, rule):
    findings = check_policy_file(os.path.join(FIXDIR, name))
    assert rule in {f.rule for f in findings}, [f.render() for f in findings]


def test_malformed_policy_file_fails_loudly(tmp_path):
    p = tmp_path / "broken.policy.json"
    p.write_text("{not json")
    findings = check_policy_file(str(p))
    assert findings and "unreadable or malformed" in findings[0].message
    p2 = tmp_path / "badprog.policy.json"
    p2.write_text(json.dumps({
        "policy": {"name": "mixed"}, "program": {"name": "nope"},
    }))
    assert "bad program" in check_policy_file(str(p2))[0].message
    # A program that constructs fine but fails at TRACE time (the loss
    # name is only checked inside the step) is still ONE finding — not a
    # traceback that aborts the CLI with later targets unchecked.
    p3 = tmp_path / "badloss.policy.json"
    p3.write_text(json.dumps({
        "policy": {"name": "mixed"},
        "program": {"name": "sgd_step", "loss": "bogus"},
    }))
    (f3,) = check_policy_file(str(p3))
    assert f3.rule == "FML601" and "bad program" in f3.message


def _run_cli(*args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "-m", "flinkml_tpu.analysis", *args,
         "--no-selfcheck"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
    )


def test_cli_format_json_and_text():
    fixture = os.path.join(
        FIXDIR, "bad_precision_fml604_bf16_psum.policy.json"
    )
    out = _run_cli(fixture, "--format", "json")
    assert out.returncode == 1
    recs = json.loads(out.stdout)
    assert {"rule", "severity", "location", "message"} <= set(recs[0])
    assert {r["rule"] for r in recs} == {"FML604"}
    # Text stays the default.
    out_text = _run_cli(fixture)
    assert out_text.returncode == 1
    assert "FML604" in out_text.stdout
    with pytest.raises(json.JSONDecodeError):
        json.loads(out_text.stdout)


# ---------------------------------------------------------------------------
# Trainer gating (sharding/apply + the estimator surface)
# ---------------------------------------------------------------------------


def _train_data(n=192, dim=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, dim)).astype(np.float32)
    y = (x @ rng.normal(size=dim) > 0).astype(np.float32) * 2 - 1
    return x, y


def test_train_linear_plan_refuses_bf16_accumulation():
    x, y = _train_data()
    mesh = DeviceMesh.for_plan(REPLICATED)
    with pytest.raises(PrecisionValidationError) as ei:
        train_linear_plan(x, y, None, REPLICATED, mesh, max_iter=1,
                          dtype="bfloat16", precision="mixed")
    assert "FML601" in {f.rule for f in ei.value.findings}


def test_train_linear_plan_refuses_policy_plan_width_conflict():
    x, y = _train_data()
    mesh = DeviceMesh.for_plan(REPLICATED)
    with pytest.raises(PrecisionValidationError) as ei:
        # f64 storage under params=float32: the plan's HBM math width
        # (8 B/elem) is not the policy's (4 B/elem).
        train_linear_plan(x, y, None, REPLICATED, mesh, max_iter=1,
                          dtype=np.float64, precision="mixed")
    assert "FML605" in {f.rule for f in ei.value.findings}


@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
def test_mixed_trainer_convergence_tolerance(optimizer):
    """The documented convergence-tolerance equivalence (precision.md):
    bf16-compute training lands within an explicit atol of its f32
    twin. Observed deviation ~3e-4; the bound is deliberately loose."""
    x, y = _train_data()
    mesh = DeviceMesh.for_plan(REPLICATED)
    kw = dict(loss="logistic", optimizer=optimizer, max_iter=20,
              learning_rate=0.3)
    golden = train_linear_plan(x, y, None, REPLICATED, mesh, **kw)
    mixed = train_linear_plan(x, y, None, REPLICATED, mesh,
                              precision="mixed", **kw)
    assert np.isfinite(mixed).all()
    np.testing.assert_allclose(mixed, golden, atol=2e-2)
    assert np.max(np.abs(mixed - golden)) > 0  # bf16 really ran


def test_mixed_trainer_fsdp_plan():
    x, y = _train_data()
    golden = train_linear_plan(
        x, y, None, REPLICATED, DeviceMesh.for_plan(REPLICATED),
        max_iter=15, learning_rate=0.3,
    )
    mixed = train_linear_plan(
        x, y, None, FSDP, DeviceMesh.for_plan(FSDP),
        max_iter=15, learning_rate=0.3, precision=MIXED,
    )
    np.testing.assert_allclose(mixed, golden, atol=2e-2)


def test_estimator_precision_knob():
    from flinkml_tpu.models.logistic_regression import LogisticRegression

    x, y = _train_data()
    t = Table({"features": x.astype(np.float64),
               "label": (y > 0).astype(np.float64)})

    def fit(**kw):
        est = LogisticRegression(**kw).set(
            LogisticRegression.FEATURES_COL, "features"
        ).set(LogisticRegression.LABEL_COL, "label").set_max_iter(10).set(
            LogisticRegression.GLOBAL_BATCH_SIZE, len(x)
        ).set(LogisticRegression.SEED, 7)
        model = est.fit(t)
        return np.asarray(model.get_model_data()[0].column("coefficient"))

    # FULL is the f32 twin at the SAME storage dtype (under x64 a
    # plan-only fit trains f64) — the A/B isolates the bf16 compute.
    base = fit(precision="full")
    mixed = fit(precision="mixed")  # no plan: rides REPLICATED
    assert np.isfinite(mixed).all()
    np.testing.assert_allclose(mixed, base, atol=2e-2)


def test_precision_unaware_estimator_refuses_at_construction():
    from flinkml_tpu.models.kmeans import KMeans

    with pytest.raises(ValueError, match="does not support precision"):
        KMeans(precision="mixed")


def test_precision_refused_on_sparse_and_host_paths():
    from flinkml_tpu.models._linear_sgd import train_linear_model_from_table
    from flinkml_tpu.models.logistic_regression import (
        train_logistic_regression,
    )
    from flinkml_tpu.linalg import SparseVector

    rows = [SparseVector(4, [0], [1.0]) for _ in range(4)]
    t = Table({"features": np.array(rows, dtype=object),
               "label": np.array([0.0, 1.0, 0.0, 1.0])})
    with pytest.raises(ValueError, match="dense path only"):
        train_linear_model_from_table(
            t, "features", "label", None, precision="mixed",
            loss="logistic", mesh=DeviceMesh(), max_iter=1,
            learning_rate=0.1, global_batch_size=4, reg=0.0,
            elastic_net=0.0, tol=0.0, seed=0,
        )
    x, y = _train_data(n=16, dim=4)
    with pytest.raises(ValueError, match="device"):
        train_logistic_regression(
            x, (y > 0).astype(np.float32), np.ones(16, np.float32),
            DeviceMesh(), 1, 0.1, 16, 0.0, 0.0, 0, mode="host",
            precision="mixed",
        )


# ---------------------------------------------------------------------------
# Fused executor gating
# ---------------------------------------------------------------------------


def _scaler_lr_pipeline(n=256, d=8, seed=3):
    from flinkml_tpu.models.logistic_regression import LogisticRegression
    from flinkml_tpu.models.scalers import StandardScaler

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    y = (x @ rng.normal(size=d) > 0).astype(np.float64)
    t = Table({"features": x, "label": y})
    sc = StandardScaler().set(StandardScaler.INPUT_COL, "features") \
                         .set(StandardScaler.OUTPUT_COL, "scaled").fit(t)
    (st,) = sc.transform(t)
    lr = LogisticRegression().set(
        LogisticRegression.FEATURES_COL, "scaled"
    ).set(LogisticRegression.LABEL_COL, "label").set_max_iter(2) \
     .set(LogisticRegression.SEED, 7).fit(st)
    return PipelineModel([sc, lr]), t


def _scaler_kmeans_pipeline(n=128, d=8, seed=4):
    from flinkml_tpu.models.kmeans import KMeans
    from flinkml_tpu.models.scalers import StandardScaler

    rng = np.random.default_rng(seed)
    t = Table({"features": rng.normal(size=(n, d))})
    sc = StandardScaler().set(StandardScaler.INPUT_COL, "features") \
                         .set(StandardScaler.OUTPUT_COL, "scaled").fit(t)
    (st,) = sc.transform(t)
    km = KMeans().set(KMeans.K, 3).set(KMeans.FEATURES_COL, "scaled") \
                 .set(KMeans.SEED, 7).fit(st)
    return PipelineModel([sc, km]), t


def test_fused_chain_mixed_inference_equivalence():
    """Pinned-numerics equivalence (precision.md recipe): decisions
    exactly equal, probabilities within the documented bf16 atol."""
    pm, t = _scaler_lr_pipeline()
    (o32,) = pm.transform(t)
    p32 = np.asarray(o32.column("prediction"))
    r32 = np.asarray(o32.column("rawPrediction"))
    with pipeline_fusion.precision_scope("mixed_inference"):
        (obf,) = pm.transform(t)
        pbf = np.asarray(obf.column("prediction"))
        rbf = np.asarray(obf.column("rawPrediction"))
    assert rbf.dtype == BF16  # bf16 really ran end-to-end
    np.testing.assert_array_equal(p32, pbf)
    np.testing.assert_allclose(
        r32.astype(np.float64), rbf.astype(np.float64), atol=2e-2
    )


def test_fused_chain_strict_mixed_keeps_f32_accumulators():
    pm, t = _scaler_lr_pipeline()
    (o32,) = pm.transform(t)
    with pipeline_fusion.precision_scope(MIXED):
        (omx,) = pm.transform(t)
        raw = np.asarray(omx.column("rawPrediction"))
    # accum=float32: the sigmoid chain downstream of the f32-accumulated
    # matmul stays f32 — tighter than the all-bf16 path.
    assert raw.dtype == np.float32
    np.testing.assert_allclose(
        np.asarray(o32.column("rawPrediction")).astype(np.float64),
        raw.astype(np.float64), atol=3e-3,
    )
    np.testing.assert_array_equal(
        np.asarray(o32.column("prediction")),
        np.asarray(omx.column("prediction")),
    )


def test_fused_chain_bf16_accumulating_kernel_refused_under_mixed():
    """The KMeans distance kernel follows plain dtype propagation, so
    its bf16 dot accumulator is refused under the STRICT policy and
    admitted under mixed_inference — the gate, not the kernel, decides."""
    pm, t = _scaler_kmeans_pipeline()
    (o0,) = pm.transform(t)
    a0 = np.asarray(o0.column("prediction"))
    with pytest.raises(PrecisionValidationError) as ei:
        with pipeline_fusion.precision_scope(MIXED):
            pm.transform(t)[0].column("prediction")
    assert "FML601" in {f.rule for f in ei.value.findings}
    with pipeline_fusion.precision_scope(MIXED_INFERENCE):
        (o1,) = pm.transform(t)
        a1 = np.asarray(o1.column("prediction"))
    np.testing.assert_array_equal(a0, a1)


def test_refused_chain_caches_no_executable():
    pm, t = _scaler_kmeans_pipeline(seed=5)
    pipeline_fusion.reset_cache()
    with pytest.raises(PrecisionValidationError):
        with pipeline_fusion.precision_scope(MIXED):
            pm.transform(t)[0].column("prediction")
    assert pipeline_fusion.compiled_program_count() == 0


def test_bf16_and_f32_programs_never_alias():
    """The would-have-aliased regression: identical chain, identical
    specs, identical bucket — the ONLY difference is the active policy.
    Without the policy in the cache key the second transform would reuse
    the first executable and the A/B would be meaningless."""
    pm, t = _scaler_lr_pipeline(seed=6)
    pipeline_fusion.reset_cache()
    (a,) = pm.transform(t)
    np.asarray(a.column("rawPrediction"))
    n_after_f32 = pipeline_fusion.compiled_program_count()
    assert n_after_f32 >= 1
    with pipeline_fusion.precision_scope("mixed_inference"):
        (b,) = pm.transform(t)
        raw_bf = np.asarray(b.column("rawPrediction"))
    assert pipeline_fusion.compiled_program_count() > n_after_f32, \
        "policy-scoped transform aliased the f32 executable"
    assert raw_bf.dtype == BF16
    # And the f32 program is untouched by the scope having existed.
    (c,) = pm.transform(t)
    assert np.asarray(c.column("rawPrediction")).dtype != BF16


def test_lazy_column_traces_under_captured_policy():
    """A lazy column's deferred program must compile under the policy
    captured at TRANSFORM time, not the reader's ambient policy: kernels
    resolve active_policy() at trace time, and the trace happens at
    first read — possibly after the scope exited (direction A) or
    inside someone else's scope (direction B, which would cache a
    never-validated bf16 program under the policy=None key)."""
    from flinkml_tpu.models.scalers import StandardScaler

    pm, t = _scaler_lr_pipeline(seed=8)
    sc2 = StandardScaler().set(StandardScaler.INPUT_COL, "rawPrediction") \
                          .set(StandardScaler.OUTPUT_COL, "rawScaled") \
                          .fit(pm.transform(t)[0])
    pm3 = PipelineModel([*pm.stages, sc2])  # rawPrediction is now lazy

    with pipeline_fusion.precision_scope("mixed_inference"):
        (o_mix,) = pm3.transform(t)
    raw_mix = np.asarray(o_mix.column("rawPrediction"))  # read post-scope
    assert raw_mix.dtype == BF16, \
        "lazy column traced under the reader's ambient policy, not the " \
        "captured one"

    pipeline_fusion.reset_cache()
    (o_plain,) = pm3.transform(t)  # no policy captured
    with pipeline_fusion.precision_scope("mixed_inference"):
        raw_plain = np.asarray(o_plain.column("rawPrediction"))
    assert raw_plain.dtype != BF16
    # The policy=None key holds the full-width executable: a later plain
    # reader gets bit-identical values, not a smuggled bf16 program.
    (o_again,) = pm3.transform(t)
    np.testing.assert_array_equal(
        raw_plain, np.asarray(o_again.column("rawPrediction"))
    )


def test_plan_step_cache_is_policy_keyed():
    """Trainer-side non-aliasing: the jitted plan-step LRU keys on the
    policy, so the bf16 and f32 steps are distinct executables while
    same-policy lookups still hit."""
    from flinkml_tpu.sharding.apply import _inner_mesh, _plan_linear_step

    mesh = _inner_mesh(DeviceMesh.for_plan(REPLICATED))
    args = (mesh, REPLICATED, "logistic", "sgd", 8, "float32",
            0.1, 0.9, 0.0, 0.0)
    f32_step = _plan_linear_step(*args, None)
    mixed_step = _plan_linear_step(*args, MIXED)
    assert f32_step is not mixed_step
    assert _plan_linear_step(*args, None) is f32_step
    assert _plan_linear_step(*args, MIXED) is mixed_step


def test_precision_scope_nests_and_restores():
    assert pipeline_fusion.active_policy() is None
    with pipeline_fusion.precision_scope("mixed"):
        assert pipeline_fusion.active_policy() is MIXED
        with pipeline_fusion.precision_scope(None):
            assert pipeline_fusion.active_policy() is None
        assert pipeline_fusion.active_policy() is MIXED
    assert pipeline_fusion.active_policy() is None


def test_precision_scope_is_thread_local():
    """A serving dispatcher scoping ITS thread must not clobber a
    concurrently transforming trainer thread's policy (and vice versa)."""
    import threading

    seen = {}

    def other_thread():
        seen["initial"] = pipeline_fusion.active_policy()
        with pipeline_fusion.precision_scope("mixed_inference"):
            seen["scoped"] = pipeline_fusion.active_policy()
            barrier.wait()   # main thread reads while we hold our scope
            barrier.wait()
        seen["after"] = pipeline_fusion.active_policy()

    barrier = threading.Barrier(2)
    with pipeline_fusion.precision_scope(MIXED):
        worker = threading.Thread(target=other_thread)
        worker.start()
        barrier.wait()
        main_during = pipeline_fusion.active_policy()
        barrier.wait()
        worker.join()
    assert seen["initial"] is None      # main's scope never leaked over
    assert seen["scoped"] is MIXED_INFERENCE
    assert seen["after"] is None
    assert main_during is MIXED         # worker's scope never leaked back


# ---------------------------------------------------------------------------
# Serving gating
# ---------------------------------------------------------------------------


def _serving_cfg(**kw):
    return ServingConfig(max_batch_rows=64, max_wait_ms=1.0,
                         warmup_row_counts=(8,), **kw)


def test_serving_engine_policy_equivalence():
    pm, t = _scaler_lr_pipeline()
    example = Table({"features": np.asarray(t.column("features"))[:8]})
    req = Table({"features": np.asarray(t.column("features"))[:32]})
    e32 = ServingEngine(pm, example, _serving_cfg(), name="f32p").start()
    try:
        r32 = e32.predict(req)
    finally:
        e32.stop()
    ebf = ServingEngine(
        pm, example, _serving_cfg(precision="mixed_inference"),
        name="bf16p",
    ).start()
    try:
        rbf = ebf.predict(req)
    finally:
        ebf.stop()
    np.testing.assert_array_equal(
        r32.column("prediction"), rbf.column("prediction")
    )
    assert rbf.column("rawPrediction").dtype == BF16
    np.testing.assert_allclose(
        r32.column("rawPrediction").astype(np.float64),
        rbf.column("rawPrediction").astype(np.float64), atol=2e-2,
    )


def test_serving_load_refused_under_strict_policy():
    pm, t = _scaler_kmeans_pipeline(seed=7)
    example = Table({"features": np.asarray(t.column("features"))[:8]})
    with pytest.raises(PrecisionValidationError):
        ServingEngine(
            pm, example, _serving_cfg(precision=MIXED), name="strict",
        ).start()


def test_serving_refused_swap_keeps_old_model(tmp_path):
    """The refuse-at-LOAD contract: a policy-violating publish fails the
    swap with the typed error and the previous model keeps serving —
    the same shape as refuse_nonfinite."""
    good, t = _scaler_lr_pipeline(seed=8)
    bad, _ = _scaler_kmeans_pipeline(seed=8)
    reg = ModelRegistry(str(tmp_path))
    v1 = reg.publish(good)
    example = Table({"features": np.asarray(t.column("features"))[:8]})
    engine = ServingEngine(
        reg, example, _serving_cfg(precision=MIXED), name="swapper",
    ).start()
    try:
        assert engine.active_version == v1
        v2 = reg.publish(bad)
        with pytest.raises(PrecisionValidationError):
            engine.swap_to(v2)
        assert engine.active_version == v1
        resp = engine.predict(
            Table({"features": np.asarray(t.column("features"))[:16]})
        )
        assert resp.version == v1
    finally:
        engine.stop()


def test_replica_pool_inherits_policy():
    from flinkml_tpu.serving.pool import ReplicaPool

    pm, t = _scaler_lr_pipeline(seed=9)
    example = Table({"features": np.asarray(t.column("features"))[:8]})
    req = Table({"features": np.asarray(t.column("features"))[:16]})
    (o32,) = pm.transform(t)
    pool = ReplicaPool(
        pm, example, config=_serving_cfg(precision="mixed_inference"),
        n_replicas=2, name="bfpool",
    ).start()
    try:
        for r in pool.replicas:
            assert r.engine._policy is MIXED_INFERENCE
        resp = pool.predict(req)
        np.testing.assert_array_equal(
            resp.column("prediction"),
            np.asarray(o32.column("prediction"))[:16],
        )
        assert resp.column("rawPrediction").dtype == BF16
    finally:
        pool.stop()


# ---------------------------------------------------------------------------
# FML106 through the shared dtype-flow walk
# ---------------------------------------------------------------------------


def _promoting_kernel(in_col="a", out_col="b"):
    strong64 = np.float64(2.0)

    def fn(cols, consts, valid):
        return {out_col: cols[in_col] * strong64}

    return ColumnKernel(
        input_cols=(in_col,), output_cols=(out_col,), fn=fn, constants={},
        fingerprint=("PromoTest", in_col, out_col),
    )


def test_promotion_findings_localize_widening_site():
    k = _promoting_kernel()
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(k.fn)(
            {"a": jax.ShapeDtypeStruct((8,), np.float32)}, {},
            jax.ShapeDtypeStruct((8,), np.float32),
        )
    findings = promotion_findings(
        closed, [np.dtype(np.float32)], {"b": np.dtype(np.float64)},
        stage="PromoTest",
    )
    assert [f.rule for f in findings] == ["FML106"]
    assert "widened at" in findings[0].message


def test_promotion_skips_wide_or_nonfloat_inputs():
    assert promotion_findings(
        None, [np.dtype(np.float64)], {"b": np.dtype(np.float64)}
    ) == []
    assert promotion_findings(
        None, [np.dtype(np.float32), np.dtype(np.int64)],
        {"b": np.dtype(np.float64)},
    ) == []
    assert promotion_findings(None, [], {"b": np.dtype(np.float64)}) == []


def test_validator_fml106_single_report_for_fused_chain():
    """Per-stage and fused-chain checks share one dtype-flow path and
    column-dedupe into ONE finding, with the widening site localized."""
    from flinkml_tpu.analysis import analyze_pipeline
    from flinkml_tpu.api import AlgoOperator

    class PromoStage(AlgoOperator):
        def __init__(self, in_col, out_col):
            super().__init__()
            self._k = _promoting_kernel(in_col, out_col)

        def transform(self, *tables):
            raise NotImplementedError

        def transform_kernel(self):
            return self._k

    from flinkml_tpu.analysis.validator import ColumnSpec

    schema = {"a": ColumnSpec(np.dtype(np.float32), ())}
    report = analyze_pipeline(
        [PromoStage("a", "b"), PromoStage("b", "c")], schema
    )
    fml106 = [f for f in report if f.rule == "FML106"]
    # b and c each flagged exactly once across both code paths.
    assert sorted(f.column for f in fml106) == ["b", "c"]
    assert all("widened at" in f.message for f in fml106)


# ---------------------------------------------------------------------------
# The int8 post-training-quantized tier (ISSUE 15)
# ---------------------------------------------------------------------------


@pytest.fixture()
def quantize_small_consts(monkeypatch):
    """Pin the int8 tier's size threshold BELOW this file's d=32 model
    constants: the committed cpu/cpu/8 table value is 256 (on a CPU
    mesh quantizing tiny vectors measured pure overhead — no HBM to
    save), which would make these quality/mechanism tests vacuous. The
    env gate is the sanctioned explicit override."""
    monkeypatch.setenv("FLINKML_TPU_INT8_MIN_CONST", "16")


def _wide_scaler_lr_pipeline(n=400, d=32, seed=11):
    """d >= the pinned quantization threshold so every model constant
    (scaler mean/scale vectors, the LR coefficient) actually
    quantizes."""
    from flinkml_tpu.models.logistic_regression import LogisticRegression
    from flinkml_tpu.models.scalers import StandardScaler

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    y = (x @ rng.normal(size=d) > 0).astype(np.float64)
    t = Table({"features": x, "label": y})
    sc = StandardScaler().set(StandardScaler.INPUT_COL, "features") \
                         .set(StandardScaler.OUTPUT_COL, "scaled").fit(t)
    (st,) = sc.transform(t)
    lr = LogisticRegression().set(
        LogisticRegression.FEATURES_COL, "scaled"
    ).set(LogisticRegression.LABEL_COL, "label").set_max_iter(3) \
     .set(LogisticRegression.SEED, 7).fit(st)
    return PipelineModel([sc, lr]), t


def test_int8_policy_value_and_roundtrip():
    assert INT8_INFERENCE.quant == "int8"
    assert not INT8_INFERENCE.mixed  # compute == params == float32
    assert resolve_policy("int8_inference") is INT8_INFERENCE
    rt = PrecisionPolicy.from_json_dict(INT8_INFERENCE.to_json_dict())
    assert rt == INT8_INFERENCE
    # quant is hashable key material: the tier can never alias FULL.
    assert hash(INT8_INFERENCE) != hash(FULL)
    assert "quant" not in FULL.to_json_dict()  # legacy files unchanged
    with pytest.raises(ValueError, match="unknown quantization"):
        PrecisionPolicy(quant="int4")


def test_quantize_absmax_per_column_properties():
    rng = np.random.default_rng(0)
    w = rng.normal(size=(24, 6)) * np.array([1, 10, 0.1, 5, 1, 1])
    q, s = quantize_absmax(w)
    assert q.dtype == np.int8 and s.dtype == np.float32
    assert s.shape == (6,)  # per LAST-axis column
    assert np.abs(q).max() <= 127
    # Error bound: half an LSB of each column's scale.
    err = np.abs(dequantize_absmax(q, s, np.float64) - w)
    assert np.all(err <= s.astype(np.float64) * 0.5 + 1e-12)
    # 1-D vectors get one per-tensor scale; zeros stay exact.
    v = np.array([0.5, -2.0, 0.0, 1.0])
    qv, sv = quantize_absmax(v)
    assert np.ndim(sv) == 0
    assert qv[2] == 0
    qz, sz = quantize_absmax(np.zeros((8, 3)))
    assert np.all(qz == 0) and np.all(sz == 1.0)


def test_int8_fused_chain_quality_tolerance_pinned(quantize_small_consts):
    """The tier's quality contract: quantization is ACTIVE (outputs
    differ from f32) yet decisions are identical and probabilities sit
    within the pinned tolerance — the absmax scheme's documented error
    envelope for this chain."""
    pm, t = _wide_scaler_lr_pipeline()
    (o32,) = pm.transform(t)
    p32 = np.asarray(o32.column("prediction"))
    r32 = np.asarray(o32.column("rawPrediction"))
    with pipeline_fusion.precision_scope("int8_inference"):
        (oq,) = pm.transform(t)
        pq = np.asarray(oq.column("prediction"))
        rq = np.asarray(oq.column("rawPrediction"))
    dev = float(np.max(np.abs(rq.astype(np.float64) - r32.astype(np.float64))))
    assert dev > 0.0, "int8 tier quantized nothing (vacuous test)"
    assert dev < 5e-3, f"int8 deviation {dev} outside the pinned tolerance"
    # Only points within dev of the decision boundary may flip.
    assert float(np.mean(p32 == pq)) >= 0.99
    # Outputs run at the tier's declared compute width (f32 — the
    # boundary casts f64 activations down, like the mixed tiers), never
    # anything narrower: dequant-fused compute, not integer math.
    assert rq.dtype == np.dtype(INT8_INFERENCE.compute)


def test_int8_program_never_aliases_f32_program(quantize_small_consts):
    pm, t = _wide_scaler_lr_pipeline(seed=12)
    pipeline_fusion.reset_cache()
    (o32,) = pm.transform(t)
    np.asarray(o32.column("prediction"))
    n_f32 = pipeline_fusion.compiled_program_count()
    with pipeline_fusion.precision_scope(INT8_INFERENCE):
        (oq,) = pm.transform(t)
        np.asarray(oq.column("prediction"))
    assert pipeline_fusion.compiled_program_count() > n_f32
    # And the f32 program still serves f32 traffic bitwise-unchanged.
    (o32b,) = pm.transform(t)
    np.testing.assert_array_equal(
        np.asarray(o32.column("rawPrediction")),
        np.asarray(o32b.column("rawPrediction")),
    )


def test_fml606_unscaled_int8_accumulation_flagged():
    def unscaled(q, x):
        return jnp.dot(x, q)  # int8 @ int8 -> int8: wraps at ±127

    q = jax.ShapeDtypeStruct((8, 8), np.int8)
    x = jax.ShapeDtypeStruct((4, 8), np.int8)
    findings = check_precision_fn(
        unscaled, q, x, policy=INT8_INFERENCE, param_argnums=(0,),
    )
    assert "FML606" in {f.rule for f in findings}

    def dequant_first(q, scale, x):
        w = q.astype(jnp.float32) * scale  # the sanctioned shape
        return jnp.dot(x, w)

    clean = check_precision_fn(
        dequant_first, q, jax.ShapeDtypeStruct((8,), np.float32),
        jax.ShapeDtypeStruct((4, 8), np.float32),
        policy=INT8_INFERENCE, param_argnums=(0, 1),
    )
    assert "FML606" not in {f.rule for f in clean}


def test_fml607_int8_params_under_full_width_policy_flagged():
    def ident(state):
        return state

    state = {"coef_q": jax.ShapeDtypeStruct((16, 16), np.int8)}
    findings = check_precision_fn(
        ident, state, policy=FULL, param_argnums=(0,),
    )
    assert "FML607" in {f.rule for f in findings}
    # Sanctioned under the quantized tier itself.
    clean = check_precision_fn(
        ident, state, policy=INT8_INFERENCE, param_argnums=(0,),
    )
    assert "FML607" not in {f.rule for f in clean}
    # Ordinary integer metadata constants (int32/int64 sizes) are NOT
    # the quantized-params shape.
    meta = {"n_categories": jax.ShapeDtypeStruct((16,), np.int64)}
    clean = check_precision_fn(
        ident, meta, policy=FULL, param_argnums=(0,),
    )
    assert clean == []


def test_serving_engine_int8_tier_end_to_end(quantize_small_consts):
    """ServingConfig(precision='int8_inference'): the engine serves the
    quantized tier within the pinned tolerance of an f32 engine, through
    the same load/warmup/FML6xx gate path as every other policy."""
    pm, t = _wide_scaler_lr_pipeline(seed=13)
    x = np.asarray(t.column("features"))
    example = Table({"features": x[:4]})
    e32 = ServingEngine(
        pm, example, ServingConfig(max_batch_rows=64, max_wait_ms=1.0),
        output_cols=("prediction", "rawPrediction"), name="p_f32",
    ).start()
    eq8 = ServingEngine(
        pm, example,
        ServingConfig(max_batch_rows=64, max_wait_ms=1.0,
                      precision="int8_inference"),
        output_cols=("prediction", "rawPrediction"), name="p_int8",
    ).start()
    try:
        r32 = e32.predict({"features": x[:32]})
        rq8 = eq8.predict({"features": x[:32]})
        np.testing.assert_array_equal(
            r32.column("prediction"), rq8.column("prediction")
        )
        dev = np.max(np.abs(
            r32.column("rawPrediction").astype(np.float64)
            - rq8.column("rawPrediction").astype(np.float64)
        ))
        assert 0.0 < dev < 5e-3, dev
    finally:
        e32.stop()
        eq8.stop()
