"""Pallas kernel backend (ISSUE 13): interpret-mode parity vs the XLA
path for each kernel, unsupported-dtype refusal, gate precedence
(env var > autotune table > static default), and the compile-cache
round-trip proving the backend is part of the program key (the
would-have-aliased regression)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flinkml_tpu import compile_cache, kernels, pipeline_fusion
from flinkml_tpu.autotune import TuningTable, mesh_key
from flinkml_tpu.autotune.table import ENV_DISABLE_VAR, ENV_TABLE_VAR
from flinkml_tpu.kernels import ENV_VAR, KernelUnsupportedError
from flinkml_tpu.kernels import chain as kchain
from flinkml_tpu.kernels import topk
from flinkml_tpu.table import Table


@pytest.fixture
def tuned_kernels(tmp_path, monkeypatch):
    """Point the process at a throwaway tuning table carrying kernel
    backend knobs (the test_autotune fixture, scoped to this family)."""
    def point_at(knobs, mesh=None):
        table = TuningTable()
        m = mesh or mesh_key()
        for knob, value in knobs.items():
            table.set_knob(m, knob, value,
                           candidates={"xla": 1.0, "pallas": 2.0},
                           source="test")
        path = str(tmp_path / "table.json")
        table.save(path)
        monkeypatch.setenv(ENV_TABLE_VAR, path)
    return point_at


@pytest.fixture
def fusion_cache():
    pipeline_fusion.reset_cache()
    saved = list(pipeline_fusion.on_compile)
    yield
    pipeline_fusion.on_compile[:] = saved
    pipeline_fusion.reset_cache()


def _chain_model(rows=200, d=5, seed=0):
    """The canonical all-kernel chain (4 scalers + logistic) and its
    input table — the fused executor's richest program."""
    from flinkml_tpu.models.logistic_regression import LogisticRegression
    from flinkml_tpu.models.scalers import (
        MaxAbsScaler, MinMaxScaler, RobustScaler, StandardScaler,
    )
    from flinkml_tpu.pipeline import PipelineModel

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, d))
    y = (x @ np.arange(1.0, d + 1) > 0).astype(np.float64)
    t = Table({"features": x, "label": y})
    stages, cur, prev = [], t, "features"
    for i, cls in enumerate(
        (StandardScaler, MinMaxScaler, MaxAbsScaler, RobustScaler), 1
    ):
        m = cls().set(cls.INPUT_COL, prev).set(cls.OUTPUT_COL, f"s{i}") \
            .fit(cur)
        (cur,) = m.transform(cur)
        prev = f"s{i}"
        stages.append(m)
    stages.append(
        LogisticRegression()
        .set(LogisticRegression.FEATURES_COL, prev)
        .set(LogisticRegression.LABEL_COL, "label")
        .set_max_iter(2).fit(cur)
    )
    return PipelineModel(stages), t


def _outputs(model, table):
    (out,) = model.transform(table)
    return {c: np.asarray(out.column(c)) for c in out.column_names
            if c not in ("features", "label")}


# -- segment-sum parity ------------------------------------------------------


@pytest.mark.parametrize("sorted_", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "float64", "bfloat16"])
def test_segment_sum_parity(sorted_, dtype):
    """Bitwise vs ``jax.ops.segment_sum`` for flat payloads: the
    unsorted kernel accumulates in element order (XLA's CPU scatter
    order) and the sorted run-flush adds left-to-right within each run
    — both reproduce the XLA result exactly at every dtype."""
    rng = np.random.default_rng(1)
    cells, dim = 700, 97
    ids = jnp.asarray(rng.integers(0, dim, cells), jnp.int32)
    if sorted_:
        ids = jnp.sort(ids)
    vals = jnp.asarray(rng.normal(size=cells)).astype(dtype)
    ref = jax.ops.segment_sum(vals, ids, num_segments=dim,
                              indices_are_sorted=sorted_)
    out = kernels.segment_sum(vals, ids, dim, indices_are_sorted=sorted_,
                              backend="pallas")
    assert out.dtype == ref.dtype
    assert np.asarray(ref).tobytes() == np.asarray(out).tobytes()


def test_segment_sum_row_payload_parity():
    """The W2V accumulator shape: [cells, k] rows scattered by id."""
    rng = np.random.default_rng(2)
    ids = jnp.asarray(rng.integers(0, 40, 300), jnp.int32)
    rows = jnp.asarray(rng.normal(size=(300, 16)).astype(np.float32))
    ref = jax.ops.segment_sum(rows, ids, num_segments=40)
    out = kernels.segment_sum(rows, ids, 40, backend="pallas")
    assert np.asarray(ref).tobytes() == np.asarray(out).tobytes()


def test_sparse_step_backend_bitwise():
    """The real consumer: one padded-ELL SGD step, Pallas scatter vs
    XLA scatter, bit-identical new coefficients."""
    from jax.sharding import Mesh, PartitionSpec as P

    from flinkml_tpu.models import _linear_sgd

    rng = np.random.default_rng(3)
    dim, bs, w = 256, 32, 5
    idx = jnp.asarray(rng.integers(0, dim, (bs, w)), jnp.int32)
    val = jnp.asarray(rng.normal(size=(bs, w)).astype(np.float32))
    y = jnp.asarray((rng.random(bs) > 0.5).astype(np.float32))
    wt = jnp.ones(bs, jnp.float32)
    coef = jnp.asarray(rng.normal(size=dim).astype(np.float32))
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    outs = {}
    for backend in ("xla", "pallas"):
        step = _linear_sgd.make_sparse_step_bucketed(
            "logistic", (bs,), "data", dim, backend)
        f = jax.jit(jax.shard_map(
            lambda c, e, i, v, yy, ww, _s=step: _s(
                c, e, i, v, yy, ww, jnp.float32(0.1), jnp.float32(0.0),
                jnp.float32(0.0),
            ),
            mesh=mesh, in_specs=(P(),) * 6, out_specs=(P(), P()),
        ))
        outs[backend] = np.asarray(
            f(coef, jnp.asarray(0, jnp.int32), idx, val, y, wt)[0]
        )
    assert outs["xla"].tobytes() == outs["pallas"].tobytes()


# -- top-k parity ------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "float64", "bfloat16"])
def test_top_k_parity(dtype):
    """Values AND indices bitwise vs ``lax.top_k``, including ties
    (both break toward the lower index) and a row count that is not a
    multiple of the kernel's row tile."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(13, 57))).astype(dtype)
    x = x.at[0, 9].set(x[0, 3])   # tie inside one row
    x = x.at[5, :].set(x[5, 0])   # fully tied row
    rv, ri = jax.lax.top_k(x, 6)
    pv, pi = topk.pallas_top_k(x, 6)
    assert np.asarray(rv).tobytes() == np.asarray(pv).tobytes()
    assert np.asarray(ri).tobytes() == np.asarray(pi).tobytes()


def test_top_k_neg_inf_rows_parity():
    """A row whose tail is -inf must walk the untaken -inf entries in
    ascending index order exactly like ``lax.top_k`` — masking the
    selected column cannot alias the remaining -inf entries (the
    duplicate-index regression)."""
    x = jnp.asarray([
        [-np.inf, 5.0, -np.inf],
        [-np.inf, -np.inf, -np.inf],
        [1.0, -np.inf, 2.0],
    ], dtype=jnp.float32)
    rv, ri = jax.lax.top_k(x, 3)
    pv, pi = topk.pallas_top_k(x, 3)
    assert np.asarray(rv).tobytes() == np.asarray(pv).tobytes()
    assert np.asarray(ri).tobytes() == np.asarray(pi).tobytes()


def test_top_k_1d_parity():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=41).astype(np.float32))
    rv, ri = jax.lax.top_k(x, 7)
    pv, pi = topk.pallas_top_k(x, 7)
    assert np.asarray(rv).tobytes() == np.asarray(pv).tobytes()
    assert np.asarray(ri).tobytes() == np.asarray(pi).tobytes()


def test_knn_backends_agree(fusion_cache, monkeypatch):
    """KNN predictions are the same whichever way a tile is ranked,
    ``lax.top_k`` (every backend but a TPU) or the kernel (a TPU;
    interpreted here): the vote consumes only the top-k indices, which
    are bitwise-equal."""
    from flinkml_tpu.models import knn
    from flinkml_tpu.models.knn import Knn

    rng = np.random.default_rng(6)
    x = rng.normal(size=(80, 4))
    y = (x[:, 0] > 0).astype(np.float64)
    t = Table({"features": x, "label": y})
    model = Knn().set(Knn.FEATURES_COL, "features") \
        .set(Knn.LABEL_COL, "label").set(Knn.K, 5).fit(t)
    q = Table({"features": rng.normal(size=(30, 4))})
    (ref,) = model.transform(q)
    ranked_by_kernel = []

    def by_kernel(d2, k):
        neg, at = topk.pallas_top_k(-d2, k, interpret=True)
        ranked_by_kernel.append(d2.shape)
        return -neg, at

    monkeypatch.setattr(knn, "_tile_top_k", by_kernel)
    # Another query count: a new trace, which sees the patched ranking.
    q2 = Table({"features": np.asarray(q.column("features"))[:29]})
    (got,) = model.transform(q2)
    assert ranked_by_kernel
    assert np.array_equal(np.asarray(ref.column("prediction"))[:29],
                          np.asarray(got.column("prediction")))


def test_lsh_ranking_pinned_order(monkeypatch):
    """The satellite fix (lsh.py host argsort → device top_k): ranking
    order equals the stable host argsort EXACTLY — ascending distance,
    ties toward the lower candidate index — through the model
    (``lax.top_k``) and with the kernel ranking the same distances."""
    from flinkml_tpu.models.lsh import MinHashLSH

    rng = np.random.default_rng(7)
    # Low-cardinality 0/1 rows manufacture many EQUAL Jaccard distances,
    # so a tie-break regression cannot hide.
    x = (rng.random((60, 12)) > 0.5).astype(np.float64)
    t = Table({"f": x})
    model = MinHashLSH().set(MinHashLSH.INPUT_COL, "f") \
        .set(MinHashLSH.OUTPUT_COL, "h") \
        .set(MinHashLSH.NUM_HASH_TABLES, 3).set_seed(11).fit(t)

    def golden(key, k):
        """The pre-fix host ranking, reproduced inline."""
        from flinkml_tpu.models.lsh import (
            _active_indices, _jaccard_distance,
        )
        rows = _active_indices(t.column("f"))
        hashes = model._hash_rows(rows)
        key_idx = np.nonzero(np.asarray(key, dtype=np.float64))[0]
        key_hash = model._hash_rows([key_idx])[0]
        cand = np.nonzero((hashes == key_hash[None, :]).any(axis=1))[0]
        dists = np.asarray([
            _jaccard_distance(rows[i], key_idx) for i in cand
        ])
        order = np.argsort(dists, kind="stable")[:k]
        return cand[order], dists[order], dists, order

    for k in (3, 7, 1000):   # 1000 > candidate count: clamp path
        want_rows, want_dists, dists, want_order = golden(x[0], k)
        got = model.approx_nearest_neighbors(t, x[0], k)
        assert np.array_equal(np.asarray(got.column("distCol")),
                              want_dists), k
        assert np.array_equal(np.asarray(got.column("f")),
                              x[want_rows]), k
        # The kernel over the same float64 distances: the same order.
        with jax.enable_x64(True):
            _, by_kernel = topk.pallas_top_k(
                jnp.asarray(-dists), min(k, dists.size))
        assert np.array_equal(np.asarray(by_kernel), want_order), k
        # duplicate distances must actually occur for the tie pin to
        # mean anything
    assert len(np.unique(golden(x[0], 1000)[1])) < \
        len(golden(x[0], 1000)[1])


# -- fused chain parity ------------------------------------------------------


@pytest.mark.parametrize("rows", [6, 50, 200])
def test_fused_chain_parity(rows, fusion_cache, monkeypatch):
    """The whole 5-stage chain through the real fused executor, Pallas
    vs XLA, bitwise at every row bucket (8 / 64 / 256 — one-tile and
    multi-tile grids)."""
    model, t = _chain_model(rows=200)
    sub = Table({c: np.asarray(t.column(c))[:rows] for c in t.column_names})
    ref = _outputs(model, sub)
    monkeypatch.setenv(ENV_VAR, "fused_chain=pallas")
    got = _outputs(model, sub)
    assert set(ref) == set(got)
    for c in ref:
        assert ref[c].dtype == got[c].dtype, c
        assert ref[c].tobytes() == got[c].tobytes(), c


def test_fused_chain_parity_bf16_policy(fusion_cache, monkeypatch):
    """Under the mixed-inference policy both backends compute at bf16;
    outputs agree within policy tolerance and decisions match away from
    the boundary (the precision-smoke contract, backend-invariant)."""
    model, t = _chain_model(rows=128)
    with pipeline_fusion.precision_scope("mixed_inference"):
        ref = _outputs(model, t)
    monkeypatch.setenv(ENV_VAR, "fused_chain=pallas")
    with pipeline_fusion.precision_scope("mixed_inference"):
        got = _outputs(model, t)
    raw_r = ref["rawPrediction"].astype(np.float64)
    raw_g = got["rawPrediction"].astype(np.float64)
    np.testing.assert_allclose(raw_r, raw_g, atol=2e-2)
    decisive = np.abs(raw_r[:, 1] - 0.5) > 2e-2
    assert decisive.any()
    assert np.array_equal(ref["prediction"][decisive],
                          got["prediction"][decisive])


def test_pallas_compile_counter(fusion_cache, monkeypatch):
    """A Pallas chain compile is visible in the executor's metrics."""
    from flinkml_tpu.utils.metrics import metrics

    model, t = _chain_model(rows=32)
    group = metrics.group("pipeline.fusion")
    before = group.snapshot()["counters"].get("pallas_compiles", 0)
    monkeypatch.setenv(ENV_VAR, "fused_chain=pallas")
    _outputs(model, t)
    after = group.snapshot()["counters"].get("pallas_compiles", 0)
    assert after > before


# -- refusal -----------------------------------------------------------------


def test_top_k_refuses_integer_dtype():
    with pytest.raises(KernelUnsupportedError, match="not floating"):
        topk.pallas_top_k(jnp.arange(10), 3)


def test_top_k_refuses_bad_k():
    x = jnp.ones((4, 8), jnp.float32)
    with pytest.raises(KernelUnsupportedError, match="outside"):
        topk.pallas_top_k(x, 9)


def test_segment_sum_refuses_integer_values():
    with pytest.raises(KernelUnsupportedError, match="not floating"):
        kernels.segment_sum(jnp.arange(8), jnp.zeros(8, jnp.int32), 4,
                            backend="pallas")


def test_chain_refuses_cross_row_kernel(monkeypatch):
    """A kernel whose output is not row-leading (a cross-row reduction)
    has no Pallas chain path: explicit request refuses loudly through
    the executor's gate."""
    from flinkml_tpu.api import ColumnKernel

    cross = ColumnKernel(
        input_cols=("x",), output_cols=("y",),
        fn=lambda cols, c, valid: {"y": jnp.sum(cols["x"], axis=0)},
        fingerprint=("crossrow",),
    )
    ext = (jnp.ones((8, 4), jnp.float32),)
    reason = kchain.unsupported_reason(
        (cross,), ("x",), ("y",), 8, None, ext, ((),), True,
    )
    assert reason is not None and "row-leading" in reason
    monkeypatch.setenv(ENV_VAR, "fused_chain=pallas")
    with pytest.raises(KernelUnsupportedError, match="row-leading"):
        pipeline_fusion._chain_backend(
            (cross,), ("x",), ("y",), 8, None, ext, ((),),
        )


def test_chain_refuses_weak_typed_constant():
    """A python-scalar (weak-typed) constant would promote differently
    through strong-typed Pallas refs — refused, never silently wrong."""
    from flinkml_tpu.api import ColumnKernel

    with jax.enable_x64(True):
        weak = jnp.asarray(2.0)   # weak float
        assert weak.weak_type
        k = ColumnKernel(
            input_cols=("x",), output_cols=("y",),
            fn=lambda cols, c, valid: {"y": cols["x"] * c["s"]},
            constants={"s": 2.0}, fingerprint=("weak",),
        )
        reason = kchain.unsupported_reason(
            (k,), ("x",), ("y",), 8, None,
            (jnp.ones((8, 4), jnp.float32),), ((weak,),), True,
        )
    assert reason is not None and "weak-typed" in reason


def test_env_var_validation(monkeypatch):
    monkeypatch.setenv(ENV_VAR, "bogus")
    with pytest.raises(ValueError, match="FLINKML_TPU_KERNELS"):
        kernels.backend_for("segment_sum")
    monkeypatch.setenv(ENV_VAR, "segment_sum=metal")
    with pytest.raises(ValueError, match="bad pair"):
        kernels.backend_for("segment_sum")
    monkeypatch.setenv(ENV_VAR, "notasite=pallas")
    with pytest.raises(ValueError, match="bad pair"):
        kernels.backend_for("segment_sum")


def test_threaded_table_choice_keeps_fallback_semantics(
    tuned_kernels, monkeypatch
):
    """Consumers resolve the gate once and re-pass the result as
    ``backend=`` (the lru-key idiom). A TABLE-chosen pallas threaded
    through that way must keep warn-and-fallback on unsupported
    operands — only a backend DISAGREEING with the gate is an explicit
    per-call request that refuses loudly."""
    tuned_kernels({"kernel_backend_segment_sum": "pallas"})
    # Simulate a compiled (non-interpret) target: float64 unsupported.
    monkeypatch.setenv(kernels.ENV_INTERPRET_VAR, "0")
    x = jnp.asarray(np.random.default_rng(8).normal(size=16))
    ids = jnp.asarray(np.arange(16) % 4, jnp.int32)
    assert x.dtype == jnp.float64
    threaded = kernels.segsum_backend()
    assert threaded == "pallas"
    # table choice threaded through: degrades to the XLA result.
    got = kernels.segment_sum(x, ids, 4, backend=threaded)
    want = jax.ops.segment_sum(x, ids, num_segments=4)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    # the same operands under a genuinely explicit request refuse.
    monkeypatch.setenv(ENV_VAR, "segment_sum=xla")   # gate now says xla ...
    with pytest.raises(KernelUnsupportedError):
        kernels.segment_sum(x, ids, 4, backend="pallas")  # ... arg disagrees


def test_table_chosen_backend_falls_back_warn_once(tuned_kernels):
    """A TABLE-chosen pallas backend degrades to XLA on unsupported
    operands (never crashes a consumer the user didn't gate) — the
    same never-crash discipline as a stale autotune entry."""
    tuned_kernels({"kernel_backend_segment_sum": "pallas"})
    assert kernels.backend_for("segment_sum") == "pallas"
    # integer values are unsupported — table choice falls back, loudly
    # in the log but without raising, and still computes correctly.
    out = kernels.segment_sum(
        jnp.arange(6), jnp.asarray([0, 0, 1, 1, 2, 2], jnp.int32), 3,
    )
    assert np.array_equal(np.asarray(out), [1, 5, 9])


# -- gate precedence ---------------------------------------------------------


def test_gate_defaults_off():
    """No env, no table entry (or the committed xla entries): every
    site resolves to XLA — Pallas is strictly opt-in-by-measurement."""
    for site in kernels.SITES:
        assert kernels.backend_for(site) == "xla"


def test_gate_precedence_env_over_table_over_default(
    tuned_kernels, monkeypatch
):
    tuned_kernels({"kernel_backend_segment_sum": "pallas"})
    # table layer supplies the default ...
    assert kernels.backend_for("segment_sum") == "pallas"
    # ... other sites keep the static default ...
    assert kernels.backend_for("fused_chain") == "xla"
    # ... the env var beats the table ...
    monkeypatch.setenv(ENV_VAR, "segment_sum=xla")
    assert kernels.backend_for("segment_sum") == "xla"
    # ... a global env value covers every site ...
    monkeypatch.setenv(ENV_VAR, "pallas")
    for site in kernels.SITES:
        assert kernels.backend_for(site) == "pallas"
    # ... and FLINKML_TPU_AUTOTUNE=0 turns the table layer off.
    monkeypatch.delenv(ENV_VAR)
    monkeypatch.setenv(ENV_DISABLE_VAR, "0")
    assert kernels.backend_for("segment_sum") == "xla"


def test_factory_backends_follow_gate(monkeypatch):
    from flinkml_tpu.models._linear_sgd import _segsum_backend

    assert _segsum_backend() == "xla"
    assert kernels.segsum_backend() == "xla"
    monkeypatch.setenv(ENV_VAR, "pallas")
    assert _segsum_backend() == "pallas"
    assert kernels.segsum_backend() == "pallas"


# -- compile cache: backend is key material ----------------------------------


def test_backend_joins_program_and_aot_cache_key(
    tmp_path, fusion_cache, monkeypatch
):
    """The would-have-aliased regression: flipping the gate must
    compile a NEW program under a key differing exactly in the backend
    element — against the in-memory cache AND the persistent AOT store
    — and flipping back must hit the original entry, not recompile."""
    keys = []
    pipeline_fusion.on_compile.append(keys.append)
    compile_cache.configure(str(tmp_path / "aot"))
    try:
        model, t = _chain_model(rows=48)
        ref = _outputs(model, t)
        n_xla = len(keys)
        assert n_xla > 0 and all(k[-1] == "xla" for k in keys)

        monkeypatch.setenv(ENV_VAR, "fused_chain=pallas")
        got = _outputs(model, t)
        pallas_keys = keys[n_xla:]
        assert pallas_keys, "gate flip did not compile a new program"
        assert all(k[-1] == "pallas" for k in pallas_keys)
        # identical but for the backend element — the would-have-aliased
        # pair.
        assert pallas_keys[0][:-1] == keys[0][:-1]
        for c in ref:
            assert ref[c].tobytes() == got[c].tobytes(), c

        # the persistent store addresses the two programs as DISTINCT
        # artifacts — and stripped of the backend element they would
        # have aliased one on-disk entry (the exact bug this guards).
        store = compile_cache.active_store()
        path_xla = store.entry_path(("pipeline_fusion", keys[0]))
        path_pallas = store.entry_path(("pipeline_fusion", pallas_keys[0]))
        assert path_xla != path_pallas
        assert store.entry_path(("pipeline_fusion", keys[0][:-1])) == \
            store.entry_path(("pipeline_fusion", pallas_keys[0][:-1]))

        # flipping back hits the original executable: zero new compiles.
        monkeypatch.delenv(ENV_VAR)
        n_before = len(keys)
        again = _outputs(model, t)
        assert len(keys) == n_before
        for c in ref:
            assert ref[c].tobytes() == again[c].tobytes(), c
    finally:
        compile_cache.reset()


def test_aot_round_trip_with_pallas_program(tmp_path, fusion_cache,
                                            monkeypatch):
    """The Pallas backend rides the AOT store's never-crash ladder:
    after dropping the in-memory layer, a re-transform either LOADS the
    serialized executable (zero compiles) or — where this jax build's
    CPU export cannot serialize the program — recompiles through the
    store's loud ``fallbacks`` path. Both legs must serve bitwise-equal
    outputs; a crash or silent wrong answer fails either way."""
    from flinkml_tpu.utils.metrics import metrics

    compile_cache.configure(str(tmp_path / "aot"))
    try:
        monkeypatch.setenv(ENV_VAR, "fused_chain=pallas")
        model, t = _chain_model(rows=48)
        ref = _outputs(model, t)
        group = metrics.group("pipeline.fusion")
        store_group = metrics.group("compile_cache")
        compiles = []
        pipeline_fusion.on_compile.append(compiles.append)
        pipeline_fusion.reset_cache()   # drop memory, keep disk
        loads_before = group.snapshot()["counters"].get("aot_loads", 0)
        got = _outputs(model, t)
        loads_after = group.snapshot()["counters"].get("aot_loads", 0)
        if compiles:
            # the store must have refused serialization LOUDLY, never
            # silently recompiled a persistable program.
            counters = store_group.snapshot()["counters"]
            assert counters.get("fallbacks", 0) > 0, counters
        else:
            assert loads_after > loads_before
        for c in ref:
            assert ref[c].tobytes() == got[c].tobytes(), c
    finally:
        compile_cache.reset()
