"""Pass 7 (memory liveness, FML70x) + the memory-aware plan/serving
wiring: the jaxpr peak-live walker, the FML701-704 rules, the
``*.memory.json`` consumer, ``infer_plan``'s quant-tier mode, and the
serving engine's load-time budget gate."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flinkml_tpu.analysis.memory import (
    DONATION_MIN_ELEMS,
    MemoryEstimate,
    check_memory_file,
    check_memory_fn,
    check_tier_ladder,
    estimate_fn_memory,
    estimate_serving_bytes,
    _probe_program,
)
from flinkml_tpu.sharding.plan import (
    BATCH_PARALLEL,
    EMBEDDING,
    FSDP,
    NoFeasiblePlanError,
    QUANT_TIER_LADDER,
    REPLICATED,
    human_bytes,
    infer_plan,
    per_device_state_bytes_tiered,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "analysis_fixtures")


# ---------------------------------------------------------------------------
# the liveness estimator
# ---------------------------------------------------------------------------

def test_estimate_counts_arguments_and_outputs():
    est = estimate_fn_memory(
        lambda x: (x * 2.0).sum(), np.zeros((1024, 8), np.float32)
    )
    assert isinstance(est, MemoryEstimate)
    assert est.argument_bytes == 1024 * 8 * 4
    assert est.output_bytes == 4  # the scalar sum
    # The undonated argument is resident for the whole program, so the
    # peak can never undercut it.
    assert est.peak_bytes >= est.argument_bytes
    assert "peak" in est.render() and "KiB" in est.render()


def test_liveness_frees_dead_intermediates():
    """A long elementwise chain must NOT estimate as the sum of every
    intermediate: each x_i dies at the next eqn, so the intermediate
    peak stays O(2 buffers), not O(chain length)."""

    def chain(x):
        for _ in range(16):
            x = x * 1.0001 + 1.0
        return x

    est = estimate_fn_memory(chain, np.zeros((4096,), np.float32))
    buf = 4096 * 4
    # 16 iterations x 2 eqns each; without last-use frees the
    # intermediate peak would be ~32 buffers.
    assert est.temp_peak_bytes <= 4 * buf


def test_donated_argument_aliases_the_update():
    """Donating the state buffer lets the update write in place: the
    peak drops by one state-sized buffer — exactly the FML703 claim."""

    def step(state, grad):
        return state - grad

    a = np.zeros((8192,), np.float32)
    undonated = estimate_fn_memory(step, a, a, param_argnums=(0,))
    donated = estimate_fn_memory(step, a, a, param_argnums=(0,),
                                 donate_argnums=(0,))
    assert donated.peak_bytes == undonated.peak_bytes - 8192 * 4


def test_params_are_sized_by_the_plan_slice():
    """Under FSDP on an 8-way axis a 1-D state leaf costs 1/8th per
    device; the batch-parallel plan replicates it."""
    state = {"coef": np.zeros((8192,), np.float32)}
    xb = np.zeros((4, 8192), np.float32)

    def step(state, xb):
        return {"coef": state["coef"] - xb.sum(0)}

    mesh = {"data": 1, "fsdp": 8}
    fsdp = estimate_fn_memory(step, state, xb, plan=FSDP, mesh=mesh,
                              param_argnums=(0,))
    repl = estimate_fn_memory(step, state, xb, plan=BATCH_PARALLEL,
                              mesh=mesh, param_argnums=(0,))
    assert fsdp.param_bytes == 8192 * 4 // 8
    assert repl.param_bytes == 8192 * 4


def test_batch_sharded_intermediates_divide_the_leading_dim():
    x = np.zeros((800, 16), np.float32)
    est = estimate_fn_memory(lambda x: (x * 2.0).sum(),
                             x, plan=BATCH_PARALLEL,
                             mesh={"data": 8})
    # ceil(800 / 8) = 100 rows per device.
    assert est.argument_bytes == 100 * 16 * 4


def test_control_flow_recursion_does_not_crash_and_adds_scratch():
    def body(c, x):
        return c + (x * 2.0).sum(), ()

    def f(xs):
        out, _ = jax.lax.scan(body, 0.0, xs)
        return jax.lax.cond(out > 0, lambda: out * 2, lambda: out)

    est = estimate_fn_memory(f, np.zeros((64, 128), np.float32))
    assert est.peak_bytes >= 64 * 128 * 4


def test_jitted_subprogram_is_walked():
    inner = jax.jit(lambda x: jnp.tanh(x) * jnp.exp(x) + jnp.sin(x))
    est = estimate_fn_memory(lambda x: inner(x).sum(),
                             np.zeros((2048,), np.float32))
    # The pjit sub-jaxpr's intermediates register as scratch.
    assert est.temp_peak_bytes >= 2048 * 4


# ---------------------------------------------------------------------------
# FML701 — peak vs budget
# ---------------------------------------------------------------------------

def test_fml701_fires_over_budget_and_is_quiet_under_it():
    fn, args, p, d = _probe_program(
        {"name": "sgd_step", "dim": 4096, "rows": 64, "donate": True}
    )
    over = check_memory_fn(fn, *args, plan=FSDP,
                           mesh={"data": 1, "fsdp": 8},
                           hbm_budget_bytes=1024, param_argnums=p,
                           donate_argnums=d, program="sgd_step")
    assert "FML701" in [f.rule for f in over]
    (f701,) = [f for f in over if f.rule == "FML701"]
    assert "KiB" in f701.message or "MiB" in f701.message
    clean = check_memory_fn(fn, *args, plan=FSDP,
                            mesh={"data": 1, "fsdp": 8},
                            hbm_budget_bytes=1 << 30, param_argnums=p,
                            donate_argnums=d, program="sgd_step")
    assert "FML701" not in [f.rule for f in clean]


# ---------------------------------------------------------------------------
# FML702 — vocab-scale hot-path intermediates
# ---------------------------------------------------------------------------

def test_fml702_flags_one_hot_densification():
    fn, args, p, d = _probe_program(
        {"name": "embedding_dense_grad", "vocab": 4096, "dim": 16,
         "rows": 32}
    )
    fs = check_memory_fn(fn, *args, plan=REPLICATED, mesh={},
                         hbm_budget_bytes=1 << 30, param_argnums=p,
                         donate_argnums=d, program="dense_grad")
    rules = [f.rule for f in fs]
    assert "FML702" in rules
    f702 = next(f for f in fs if f.rule == "FML702")
    assert "4096" in f702.message


def test_fml702_exempts_batch_sized_lookup_and_state_output():
    """The contract shape — gather batch rows, scatter the update back.
    The updated table is a program OUTPUT (sanctioned state), so only a
    dying vocab-scale intermediate may flag."""
    fn, args, p, d = _probe_program(
        {"name": "embedding_lookup", "vocab": 4096, "dim": 16, "rows": 32}
    )
    fs = check_memory_fn(fn, *args, plan=REPLICATED, mesh={},
                         hbm_budget_bytes=1 << 30, param_argnums=p,
                         donate_argnums=d, program="lookup")
    assert [f.rule for f in fs] == []

    def scatter_update(state, ids, delta):
        table = state["emb/embedding"]
        return {"emb/embedding": table.at[ids].add(delta)}

    vocab, dim, rows = 4096, 16, 32
    table = jax.ShapeDtypeStruct((vocab, dim), np.float32)
    ids = jax.ShapeDtypeStruct((rows,), np.int32)
    delta = jax.ShapeDtypeStruct((rows, dim), np.float32)
    fs = check_memory_fn(scatter_update, {"emb/embedding": table}, ids,
                         delta, plan=REPLICATED, mesh={},
                         hbm_budget_bytes=1 << 30, param_argnums=(0,),
                         donate_argnums=(0,), program="scatter_update")
    assert "FML702" not in [f.rule for f in fs]


def test_fml702_ignores_small_tables():
    """A tiny table's whole-row intermediate is not "vocab-scale"."""

    def dense(state, ids, grad):
        table = state["t/embedding"]
        oh = jax.nn.one_hot(ids, table.shape[0], dtype=table.dtype)
        return {"t/embedding": table + oh.T @ grad}

    table = jax.ShapeDtypeStruct((64, 8), np.float32)  # < min rows
    ids = jax.ShapeDtypeStruct((4,), np.int32)
    grad = jax.ShapeDtypeStruct((4, 8), np.float32)
    fs = check_memory_fn(dense, {"t/embedding": table}, ids, grad,
                         plan=REPLICATED, mesh={},
                         hbm_budget_bytes=1 << 30, param_argnums=(0,),
                         donate_argnums=(0,), program="tiny")
    assert "FML702" not in [f.rule for f in fs]


# ---------------------------------------------------------------------------
# FML703 — undonated same-shape state updates (live, on the real step)
# ---------------------------------------------------------------------------

def test_fml703_live_on_undonated_sgd_step():
    """The REAL training step (sharding.apply.linear_step_fn) traced
    without donation flags every same-shape state leaf; with donation it
    is clean — the exact missed-donate_argnums shape, demonstrated on
    the program the product actually compiles."""
    fn, args, p, d = _probe_program(
        {"name": "sgd_step", "dim": 4096, "rows": 64, "donate": False}
    )
    fs = check_memory_fn(fn, *args, plan=REPLICATED, mesh={},
                         hbm_budget_bytes=1 << 30, param_argnums=p,
                         donate_argnums=d, program="sgd_step")
    cols = sorted(f.column for f in fs if f.rule == "FML703")
    assert cols == ["coef", "momentum"]
    fn, args, p, d = _probe_program(
        {"name": "sgd_step", "dim": 4096, "rows": 64, "donate": True}
    )
    fs = check_memory_fn(fn, *args, plan=REPLICATED, mesh={},
                         hbm_budget_bytes=1 << 30, param_argnums=p,
                         donate_argnums=d, program="sgd_step")
    assert [f.rule for f in fs] == []


def test_fml703_adam_flags_every_slot_but_not_the_step_counter():
    fn, args, p, d = _probe_program(
        {"name": "adam_step", "dim": 512, "rows": 16, "donate": False}
    )
    fs = check_memory_fn(fn, *args, plan=REPLICATED, mesh={},
                         hbm_budget_bytes=1 << 30, param_argnums=p,
                         donate_argnums=d, program="adam_step")
    cols = sorted(f.column for f in fs if f.rule == "FML703")
    # coef/m/v flag; the scalar step counter is below the elems floor.
    assert cols == ["coef", "m", "v"]
    assert 512 >= DONATION_MIN_ELEMS


# ---------------------------------------------------------------------------
# FML704 — no tier fits
# ---------------------------------------------------------------------------

def test_fml704_lists_every_tier_footprint():
    fs = check_tier_ladder({"data": 1, "fsdp": 8},
                           {"emb/embedding": (1 << 20, 64)}, 4096)
    assert [f.rule for f in fs] == ["FML704"]
    msg = fs[0].message
    for tier in QUANT_TIER_LADDER:
        assert f"@{tier}" in msg
    assert "at any quant tier" in msg and "MiB" in msg


def test_tier_ladder_quiet_when_a_tier_fits():
    shapes = {"emb/embedding": (1 << 14, 64)}
    # f32 fsdp footprint: (2^14/8)*64*4*2 = 1 MiB -> a 2 MiB budget fits.
    assert check_tier_ladder({"data": 1, "fsdp": 8}, shapes, 2 << 20) == []


# ---------------------------------------------------------------------------
# infer_plan memory-aware mode
# ---------------------------------------------------------------------------

def test_infer_plan_tiered_returns_plan_and_tier():
    shapes = {"coef": (8192,)}
    plan, tier = infer_plan({"data": 1, "fsdp": 8}, shapes, 1 << 20,
                            quant_tiers=True)
    assert plan.name == "batch_parallel" and tier == "float32"


def test_infer_plan_routes_over_budget_f32_to_int8():
    """The ROADMAP item 3 shape: a parameter universe infeasible at f32
    re-runs the footprint against the quantized widths and CHOOSES
    quantization to fit the budget."""
    mesh = {"data": 1, "fsdp": 8, "tp": 1}
    shapes = {"emb/embedding": (1 << 16, 64)}
    # Serving footprints (no optimizer slots): int8 stores 1 B codes, so
    # it sits BELOW bf16 — slots would stay f32 and invert the order.
    bf16 = per_device_state_bytes_tiered(FSDP, mesh, shapes, "bfloat16",
                                         optimizer_slots=0)
    int8 = per_device_state_bytes_tiered(FSDP, mesh, shapes, "int8",
                                         optimizer_slots=0)
    assert int8 < bf16
    budget = (bf16 + int8) // 2  # below every float tier, above int8
    with pytest.raises(NoFeasiblePlanError):
        infer_plan(mesh, shapes, budget, optimizer_slots=0)  # f32 mode
    plan, tier = infer_plan(mesh, shapes, budget, optimizer_slots=0,
                            quant_tiers=True)
    assert tier == "int8"
    assert per_device_state_bytes_tiered(
        plan, mesh, shapes, tier, optimizer_slots=0
    ) <= budget


def test_tiered_footprint_math():
    mesh = {"data": 1, "fsdp": 8}
    shapes = {"emb/embedding": (1024, 64)}
    slice_elems = (1024 // 8) * 64
    assert per_device_state_bytes_tiered(FSDP, mesh, shapes, "float32") \
        == 4 * slice_elems * 2
    assert per_device_state_bytes_tiered(FSDP, mesh, shapes, "bfloat16") \
        == 2 * slice_elems * 2
    # int8: 1 B codes + 4 B x 64 scale columns; the slot stays f32.
    assert per_device_state_bytes_tiered(FSDP, mesh, shapes, "int8") \
        == (slice_elems + 4 * 64) + 4 * slice_elems
    with pytest.raises(ValueError, match="unknown quant tier"):
        per_device_state_bytes_tiered(FSDP, mesh, shapes, "int4")


def test_no_feasible_plan_message_is_human():
    with pytest.raises(NoFeasiblePlanError) as ei:
        infer_plan({"data": 1, "fsdp": 8}, {"coef": (1 << 22,)}, 1000)
    msg = str(ei.value)
    assert "MiB" in msg and " B)" in msg  # human units + raw parens
    # the budget is stated ONCE (in the header), not per candidate
    assert msg.count("hbm_budget_bytes") == 1


# ---------------------------------------------------------------------------
# *.memory.json consumer + CLI
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rule,path", [
    ("FML701", "bad_memory_fml701_over_budget.memory.json"),
    ("FML702", "bad_memory_fml702_dense_grad.memory.json"),
    ("FML703", "bad_memory_fml703_undonated.memory.json"),
    ("FML704", "bad_memory_fml704_no_tier_fits.memory.json"),
])
def test_seeded_memory_fixtures_flag_their_rule(rule, path):
    findings = check_memory_file(os.path.join(FIXTURES, path))
    assert rule in [f.rule for f in findings]


def test_unreadable_memory_file_fails_loudly(tmp_path):
    bad = tmp_path / "broken.memory.json"
    bad.write_text("{not json")
    assert [f.rule for f in check_memory_file(str(bad))] == ["FML701"]
    empty = tmp_path / "empty.memory.json"
    empty.write_text("{}")  # neither a program nor a tier ladder
    assert [f.rule for f in check_memory_file(str(empty))] == ["FML701"]
    badprog = tmp_path / "prog.memory.json"
    badprog.write_text(json.dumps(
        {"program": {"name": "nonsense_step"}}
    ))
    assert [f.rule for f in check_memory_file(str(badprog))] == ["FML701"]


def test_cli_runs_the_memory_pass_and_dir_walk_finds_fixtures(capsys):
    from flinkml_tpu.analysis.__main__ import main

    fixture = os.path.join(
        FIXTURES, "bad_memory_fml701_over_budget.memory.json"
    )
    assert main([fixture, "--no-selfcheck"]) == 1
    capsys.readouterr()  # drop the text report
    # The extension->bucket walk picks .memory.json out of a directory
    # target (the refactor's whole point: one table, no missed ext).
    assert main([FIXTURES, "--no-selfcheck", "--format", "json"]) == 1
    found = json.loads(capsys.readouterr().out)
    assert {"FML701", "FML702", "FML703", "FML704"} <= \
        {f["rule"] for f in found}


# ---------------------------------------------------------------------------
# calibration vs XLA's own memory_analysis
# ---------------------------------------------------------------------------

def _toy_twin():
    def f(x):
        h = jnp.tanh(x @ x.T)
        return (h * h).sum()

    x = np.zeros((256, 256), np.float32)
    return jax.jit(f).lower(x).compile(), estimate_fn_memory(f, x)


def _fused_chain_twin(n=8192, d=32):
    """The five-stage scoring chain's arithmetic (four scalers and the
    logistic head) as one program on one device."""
    def chain(x, mean, std, dmin, dmax, maxabs, median, rng_, coef):
        h = (x - mean) / std
        h = (h - dmin) / (dmax - dmin)
        h = h / maxabs
        h = (h - median) / rng_
        return jax.nn.sigmoid(h @ coef)

    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, d)).astype(np.float32)
    row = np.ones((1, d), np.float32)
    coef = rng.normal(size=(d,)).astype(np.float32)
    args = (x, row, row, 0 * row, row, row, 0 * row, row, coef)
    return (jax.jit(chain).lower(*args).compile(),
            estimate_fn_memory(chain, *args))


def _sharded_sgd_step_twin(state_dim=65536, bs=256):
    """The plan-sharded SGD step the product trains with, its state
    donated, FSDP over the eight-device mesh."""
    from flinkml_tpu.parallel import DeviceMesh
    from flinkml_tpu.sharding.apply import (
        batch_sharding, init_linear_state, linear_step_fn, state_shardings,
    )

    mesh = DeviceMesh.for_plan(FSDP)
    step = linear_step_fn(
        loss="logistic", optimizer="sgd", dtype_name="float32",
        learning_rate=0.1, momentum=0.9, reg_l2=0.0, reg_l1=0.0,
    )
    state = init_linear_state(state_dim, "sgd", np.float32)
    rng = np.random.default_rng(0)
    args = (state, rng.normal(size=(bs, state_dim)).astype(np.float32),
            (rng.random(bs) > 0.5).astype(np.float32),
            np.ones(bs, np.float32))
    b_shard = batch_sharding(FSDP, mesh)
    compiled = jax.jit(
        step,
        in_shardings=(state_shardings(FSDP, mesh, state),
                      b_shard, b_shard, b_shard),
        donate_argnums=(0,),
    ).lower(*args).compile()
    return compiled, estimate_fn_memory(
        step, *args, plan=FSDP, mesh=dict(mesh.mesh.shape),
        param_argnums=(0,), donate_argnums=(0,),
    )


@pytest.mark.parametrize(
    "twin", [_toy_twin, _fused_chain_twin, _sharded_sgd_step_twin])
def test_estimate_calibrated_against_xla_memory_analysis(twin):
    """The static estimate stays inside 0.5x-2.0x of what XLA reports for
    the compiled program (temporaries, arguments and outputs): on a toy,
    and on the two programs the band was set on."""
    compiled, est = twin()
    ma = compiled.memory_analysis()
    actual = (int(ma.temp_size_in_bytes) + int(ma.argument_size_in_bytes)
              + int(ma.output_size_in_bytes))
    assert 0.5 * actual <= est.peak_bytes <= 2.0 * actual, (
        f"estimate {est.peak_bytes} vs XLA {actual}"
    )


# ---------------------------------------------------------------------------
# serving load-time budget gate
# ---------------------------------------------------------------------------

def test_estimate_serving_bytes_tier_ordering():
    from flinkml_tpu.models.logistic_regression import (
        LogisticRegressionModel,
    )
    from flinkml_tpu.table import Table

    d = 64
    lr = LogisticRegressionModel().set(
        LogisticRegressionModel.FEATURES_COL, "features"
    )
    lr.set_model_data(Table({"coefficient": np.ones((1, d))}))
    schema = {"features": (np.dtype(np.float64), (d,))}
    full = estimate_serving_bytes(lr, schema, 64, policy=None)
    int8 = estimate_serving_bytes(lr, schema, 64,
                                  policy="int8_inference")
    mixed = estimate_serving_bytes(lr, schema, 64,
                                   policy="mixed_inference")
    assert int8 < full and mixed < full
    assert full > 3 * 64 * d * 8  # three batch buffers floor


def test_serving_budget_gate_refuses_swap_and_keeps_old_model(tmp_path):
    from flinkml_tpu.models.logistic_regression import (
        LogisticRegression,
        LogisticRegressionModel,
    )
    from flinkml_tpu.serving import (
        ModelRegistry,
        ServingConfig,
        ServingEngine,
        ServingMemoryError,
    )
    from flinkml_tpu.table import Table

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 8))
    y = (x @ rng.normal(size=8) > 0).astype(np.float64)
    small = LogisticRegression().set(
        LogisticRegression.FEATURES_COL, "features"
    ).set(LogisticRegression.LABEL_COL, "label").set_max_iter(3).fit(
        Table({"features": x, "label": y})
    )
    # v2: finite (passes the sentinel) but with a multi-MiB learned
    # array — over any KiB-scale budget. It is refused BEFORE warmup,
    # so it never has to transform.
    big = LogisticRegressionModel().set(
        LogisticRegressionModel.FEATURES_COL, "features"
    )
    big.set_model_data(
        Table({"coefficient": np.ones((1, 1 << 20))})
    )

    reg = ModelRegistry(str(tmp_path / "reg"))
    v1 = reg.publish(small)
    eng = ServingEngine(
        reg, Table({"features": x[:4]}),
        ServingConfig(max_batch_rows=64, warmup_row_counts=(4,),
                      hbm_budget_bytes=1 << 20),
        output_cols=("prediction",),
    ).start()
    try:
        assert eng.predict(Table({"features": x[:4]})).version == v1
        v2 = reg.publish(big)
        with pytest.raises(ServingMemoryError, match="keeps serving"):
            eng.swap_to(v2)
        # The refused swap left v1 active and serving.
        assert eng.predict(Table({"features": x[:4]})).version == v1
    finally:
        eng.stop()


def test_human_bytes_rendering():
    assert human_bytes(12 * (1 << 20)) == "12.00 MiB (12582912 B)"
    assert human_bytes(512) == "512 B"
    assert human_bytes(1 << 30) == "1.00 GiB (1073741824 B)"


def test_fml503_messages_are_humanized():
    from flinkml_tpu.analysis.sharding_check import check_plan

    findings = check_plan(
        REPLICATED, {"data": 8},
        param_shapes={"emb/embedding": (1 << 20, 64)},
        hbm_budget_bytes=1 << 20,
    )
    f503 = [f for f in findings if f.rule == "FML503"]
    assert f503 and all(
        "MiB" in f.message and " B)" in f.message for f in f503
    )
