"""Clean-process scenarios behind ``tests/test_compile_cache.py``.

Why a child process: once jax's persistent compilation cache LOADS one
executable in a process, XLA:CPU registers that executable's jit-kernels
as resident-but-not-re-emittable, and every LATER compile sharing a
content-identical kernel serializes without it ("Symbols not found" at
deserialize — the store's post-serialize load check refuses such
artifacts by design). The suite's conftest enables that cache for speed,
so deterministic store round-trips must run in a process that never
touched it — which is also exactly the production cold-start shape the
subsystem exists for. This script runs every serialization-dependent
scenario in one fresh interpreter and prints a JSON report; the pytest
module asserts over it.
"""

import json
import logging
import os
import shutil
import sys
import tempfile
import threading
import time


def _outputs(model, table):
    import numpy as np

    (out,) = model.transform(table)
    return {
        c: np.asarray(out.column(c))
        for c in out.column_names if c not in ("features", "label")
    }


def _fitted_chain(n=520, d=11, seed=0):
    import numpy as np

    from flinkml_tpu.models.logistic_regression import LogisticRegression
    from flinkml_tpu.models.scalers import MinMaxScaler, StandardScaler
    from flinkml_tpu.pipeline import PipelineModel
    from flinkml_tpu.table import Table

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    y = (x @ rng.normal(size=d) > 0).astype(np.float64)
    train = Table({"features": x, "label": y})
    scaler = (StandardScaler().set(StandardScaler.INPUT_COL, "features")
              .set(StandardScaler.OUTPUT_COL, "s1").fit(train))
    (t1,) = scaler.transform(train)
    mm = (MinMaxScaler().set(MinMaxScaler.INPUT_COL, "s1")
          .set(MinMaxScaler.OUTPUT_COL, "s2").fit(t1))
    (t2,) = mm.transform(t1)
    lr = (LogisticRegression()
          .set(LogisticRegression.FEATURES_COL, "s2")
          .set(LogisticRegression.LABEL_COL, "label")
          .set_max_iter(2).fit(t2))
    return PipelineModel([scaler, mm, lr]), x


def main() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    jax.config.update("jax_enable_x64", True)  # match the suite semantics

    import numpy as np

    from flinkml_tpu import compile_cache, pipeline_fusion
    from flinkml_tpu.compile_cache.store import CompileCacheStore
    from flinkml_tpu.table import Table
    from flinkml_tpu.utils.metrics import metrics

    warnings: list = []
    handler = logging.Handler()
    handler.emit = lambda record: warnings.append(record.getMessage())
    logging.getLogger("flinkml_tpu.compile_cache").addHandler(handler)

    def counters():
        return dict(metrics.group("compile_cache").snapshot()["counters"])

    def fresh(store_dir):
        compile_cache.reset()
        if store_dir is not None:
            compile_cache.configure(store_dir)
        else:
            compile_cache.configure(None)
        pipeline_fusion.reset_cache()

    report: dict = {}
    root = tempfile.mkdtemp(prefix="cc-child-")
    model, x = _fitted_chain()
    table = Table({"features": x, "label": np.zeros(len(x))})

    # -- scenario: disk round trip + bitwise parity -------------------------
    fresh(None)
    baseline = _outputs(model, table)
    d1 = os.path.join(root, "roundtrip")
    before = counters()
    fresh(d1)
    cold = _outputs(model, table)
    after_cold = counters()
    fresh(d1)  # "fresh process": same dir, dropped memory + program caches
    warm = _outputs(model, table)
    after_warm = counters()
    report["roundtrip"] = {
        "stores": after_cold.get("stores", 0) - before.get("stores", 0),
        "aot_files": sum(1 for _, _, fs in os.walk(d1)
                         for f in fs if f.endswith(".aot")),
        "warm_hits": after_warm.get("hits", 0) - after_cold.get("hits", 0),
        "warm_extra_misses": after_warm.get("misses", 0)
        - after_cold.get("misses", 0),
        "cold_bitwise": all(baseline[c].tobytes() == cold[c].tobytes()
                            for c in baseline),
        "warm_bitwise": all(baseline[c].tobytes() == warm[c].tobytes()
                            for c in baseline),
    }

    # -- scenario: corrupt/torn entries fall back loudly --------------------
    paths = [os.path.join(r, f) for r, _, fs in os.walk(d1)
             for f in fs if f.endswith(".aot")]
    for p in paths:
        with open(p, "r+b") as fh:
            fh.truncate(max(1, os.path.getsize(p) // 2))
    fresh(d1)
    n_warn = len(warnings)
    before = counters()
    served = _outputs(model, table)
    after = counters()
    fresh(d1)  # the corrupt files must have been replaced by good ones
    before_reread = counters()
    _outputs(model, table)
    after_reread = counters()
    report["corrupt"] = {
        "corrupt_entries": after.get("corrupt_entries", 0)
        - before.get("corrupt_entries", 0),
        "torn_files": len(paths),
        "served_bitwise": all(baseline[c].tobytes() == served[c].tobytes()
                              for c in baseline),
        "warned": any("corrupt compile-cache entry" in w
                      for w in warnings[n_warn:]),
        "rewritten_hits": after_reread.get("hits", 0)
        - before_reread.get("hits", 0),
    }

    # -- scenario: env-fingerprint mismatch refuses a copied entry ----------
    store = compile_cache.active_store()
    env_dir = os.path.dirname(store.entry_path(("probe",)))
    entries = [f for f in os.listdir(env_dir) if f.endswith(".aot")]
    bumped = CompileCacheStore(d1)
    bumped._env = dict(store._environment())
    bumped._env["jax"] = "999.0.0"
    new_dir = os.path.dirname(bumped.entry_path(("probe",)))
    os.makedirs(new_dir, exist_ok=True)
    target = bumped.entry_path(("alien",))
    shutil.copy(os.path.join(env_dir, entries[0]), target)
    before = counters()
    refused = bumped._read_disk(("alien",)) is None
    after = counters()
    report["env_mismatch"] = {
        "namespaces_differ": new_dir != env_dir,
        "copied_entry_refused": refused,
        "env_mismatches": after.get("env_mismatches", 0)
        - before.get("env_mismatches", 0),
    }

    # -- scenario: racing compilers share one build -------------------------
    import jax.numpy as jnp

    builds: list = []

    def build():
        builds.append(threading.get_ident())
        time.sleep(0.05)
        return jax.jit(lambda v: jnp.sin(v * 1.2345678) * 2.0).lower(
            np.ones(19, np.float32)
        ).compile()

    race_store = CompileCacheStore(os.path.join(root, "race"))
    results: list = []
    threads = [
        threading.Thread(target=lambda: results.append(
            race_store.get_or_compile(("race-key",), build,
                                      device_ids=(0,))
        ))
        for _ in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    builds_one_store = len(builds)
    # two independent stores (processes) racing on one path
    s1 = CompileCacheStore(os.path.join(root, "race"))
    s2 = CompileCacheStore(os.path.join(root, "race"))
    t1 = threading.Thread(target=s1.get_or_compile,
                          args=(("race-key-2",), build),
                          kwargs={"device_ids": (0,)})
    t2 = threading.Thread(target=s2.get_or_compile,
                          args=(("race-key-2",), build),
                          kwargs={"device_ids": (0,)})
    t1.start(); t2.start(); t1.join(); t2.join()
    fresh_store = CompileCacheStore(os.path.join(root, "race"))
    program, outcome = fresh_store.get_or_compile(
        ("race-key-2",), build, device_ids=(0,)
    )
    expect = np.sin(np.ones(19, np.float32) * 1.2345678) * 2.0
    report["race"] = {
        "racing_threads": 4,
        "results": len(results),
        "builds_one_store": builds_one_store,
        "compiled_outcomes": [o for _, o in results].count("compiled"),
        "reload_outcome": outcome,
        "reload_correct": bool(np.allclose(np.asarray(program(
            np.ones(19, np.float32))), expect, rtol=1e-6)),
    }

    # -- scenario: pool spin-up pays one compile per program ----------------
    from flinkml_tpu.serving.engine import ServingConfig
    from flinkml_tpu.serving.pool import ReplicaPool

    d2 = os.path.join(root, "pool")
    fresh(d2)
    before = counters()
    compiles: list = []
    pipeline_fusion.on_compile.append(compiles.append)
    pool = ReplicaPool(
        model, Table({"features": x[:4], "label": np.zeros(4)}),
        config=ServingConfig(max_batch_rows=16, max_wait_ms=1.0),
        n_replicas=4, name="cc-pool",
    ).start()
    n_programs = len(compiles)
    after = counters()
    resp = pool.predict({"features": x[:5], "label": np.zeros(5)})
    steady = len(compiles)
    direct = {c: v[:5] for c, v in _outputs(model, table).items()}
    pool_bitwise = all(
        resp.columns[c].tobytes() == direct[c].tobytes()
        for c in resp.columns
    )
    pool.stop(drain=False)
    pipeline_fusion.on_compile.remove(compiles.append)
    report["pool"] = {
        "programs": n_programs,
        "misses": after.get("misses", 0) - before.get("misses", 0),
        "hits": after.get("hits", 0) - before.get("hits", 0),
        "retarget_loads": after.get("retarget_loads", 0)
        - before.get("retarget_loads", 0),
        "steady_state_compiles": steady - n_programs,
        "bitwise_vs_direct": pool_bitwise,
    }

    # -- scenario: cross-device retargeted load parity ----------------------
    d3 = os.path.join(root, "retarget")
    fresh(d3)
    before = counters()
    _outputs(model, table)  # compile + store on the default device
    with jax.default_device(jax.devices()[3]):
        # A FRESH table: the shared one's device cache already holds
        # dev0-resident buffers, which would dodge the retarget path.
        pinned = _outputs(
            model, Table({"features": x, "label": np.zeros(len(x))})
        )
    after = counters()
    report["retarget"] = {
        "retarget_loads": after.get("retarget_loads", 0)
        - before.get("retarget_loads", 0),
        "bitwise": all(baseline[c].tobytes() == pinned[c].tobytes()
                       for c in baseline),
    }

    # -- scenario: the plan-sharded step round-trips ------------------------
    from flinkml_tpu.parallel import DeviceMesh
    from flinkml_tpu.sharding.apply import (
        _plan_linear_step,
        train_linear_plan,
    )
    from flinkml_tpu.sharding.plan import FSDP

    rng = np.random.default_rng(0)
    px = rng.normal(size=(272, 48)).astype(np.float32)
    py = (px @ rng.normal(size=48).astype(np.float32) > 0).astype(np.float32)
    mesh = DeviceMesh.for_plan(FSDP)
    fresh(None)
    coef0 = train_linear_plan(px, py, None, FSDP, mesh, max_iter=4)
    d4 = os.path.join(root, "plan")
    fresh(d4)
    _plan_linear_step.cache_clear()
    before = counters()
    coef_cold = train_linear_plan(px, py, None, FSDP, mesh, max_iter=4)
    after_cold = counters()
    fresh(d4)
    _plan_linear_step.cache_clear()
    coef_warm = train_linear_plan(px, py, None, FSDP, mesh, max_iter=4)
    after_warm = counters()
    _plan_linear_step.cache_clear()
    report["plan_step"] = {
        "cold_misses": after_cold.get("misses", 0)
        - before.get("misses", 0),
        "cold_stores": after_cold.get("stores", 0)
        - before.get("stores", 0),
        "warm_hits": after_warm.get("hits", 0)
        - after_cold.get("hits", 0),
        "cold_equal": bool(np.array_equal(coef0, coef_cold)),
        "warm_equal": bool(np.array_equal(coef0, coef_warm)),
    }

    shutil.rmtree(root, ignore_errors=True)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
