"""Clean-process autoscale scenario behind ``tests/test_autoscaler.py``.

Why a child process: the scale-up acceptance ("a new replica warms via
compile-cache retarget loads — zero new XLA compiles in-process") is
serialization-dependent, and the suite conftest's jax persistent cache
poisons XLA:CPU executable serialization process-wide (the PR 11
finding documented in ``tests/_compile_cache_child.py``). This script
runs the scenario in a fresh interpreter — which is also the production
shape: a serving process that autoscales never touched the test cache —
and prints a JSON report the pytest module asserts over.
"""

import json
import os
import sys
import tempfile


def main() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    jax.config.update("jax_enable_x64", True)

    import numpy as np

    from flinkml_tpu import compile_cache, pipeline_fusion
    from flinkml_tpu.models.logistic_regression import LogisticRegression
    from flinkml_tpu.models.scalers import StandardScaler
    from flinkml_tpu.pipeline import PipelineModel
    from flinkml_tpu.serving import ReplicaPool, ServingConfig
    from flinkml_tpu.table import Table
    from flinkml_tpu.utils.metrics import metrics

    rng = np.random.default_rng(0)
    x = rng.normal(size=(400, 8))
    y = (x @ rng.normal(size=8) > 0).astype(np.float64)
    train = Table({"features": x, "label": y})
    sc = (StandardScaler().set(StandardScaler.INPUT_COL, "features")
          .set(StandardScaler.OUTPUT_COL, "scaled").fit(train))
    (t2,) = sc.transform(train)
    lr = (LogisticRegression()
          .set(LogisticRegression.FEATURES_COL, "scaled")
          .set(LogisticRegression.LABEL_COL, "label")
          .set_max_iter(3).fit(t2))
    model = PipelineModel([sc, lr])

    store_dir = tempfile.mkdtemp(prefix="autoscale-child-")
    compile_cache.configure(store_dir)

    def counters():
        return dict(
            metrics.group("pipeline.fusion").snapshot()["counters"]
        )

    pool = ReplicaPool(
        model, Table({"features": x[:4]}),
        config=ServingConfig(max_batch_rows=32, max_queue_rows=256,
                             max_wait_ms=1.0),
        n_replicas=2, output_cols=("prediction",), name="child_pool",
    ).start()
    baseline = np.asarray(
        pool.predict({"features": x[:16]}).column("prediction")
    )
    after_start = counters()

    # The autoscaler's scale-up path, twice (fresh devices each time).
    r2 = pool.add_replica()
    r3 = pool.add_replica()
    after_scale = counters()

    # The new replicas serve, bitwise-identically (route to them
    # directly through their engines — the pool's router would balance).
    scaled_preds = [
        np.asarray(r.engine.predict(
            {"features": x[:16]}).column("prediction"))
        for r in (r2, r3)
    ]
    parity = all(np.array_equal(baseline, p) for p in scaled_preds)
    pool.stop()

    print(json.dumps({
        "compiles_after_start": after_start.get("compiles", 0),
        "compiles_after_scale": after_scale.get("compiles", 0),
        "new_compiles_on_scale_up": (
            after_scale.get("compiles", 0) - after_start.get("compiles", 0)
        ),
        "aot_loads_on_scale_up": (
            after_scale.get("aot_loads", 0) - after_start.get("aot_loads", 0)
        ),
        "scaled_replica_parity_bitwise": bool(parity),
        "replicas": 4,
    }))


if __name__ == "__main__":
    sys.exit(main())
