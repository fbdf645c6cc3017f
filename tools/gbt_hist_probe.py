"""Device A/B: GBT per-level histogram layouts at the bench shape.

The roofline audit (BASELINE.md "rooflines") measured the GBT stage at
0.22% of its streaming bound and diagnosed the per-level sort-based
``segment_sum`` over n·d cells — the same class as sparse LR. The
``cumsum`` layout sorts cells ONCE at pack time by the static
(feature, bin) key and reduces each level's 2^level-wide node-one-hot
expansion with chunked run totals (streaming passes, no sort).

Runs the bench GBT stage (262k rows, 16 features, 32 bins, depth 4,
20 trees) once per layout through the product builder; the winner sets
the FLINKML_TPU_GBT_HISTOGRAM default. Forests are verified identical
(same split features across layouts) before timing is trusted.
"""

import time

import numpy as np


N, D, BINS, DEPTH, TREES = 262_144, 16, 32, 4, 20


def run(layout):
    import jax
    import jax.numpy as jnp
    from flinkml_tpu.models.gbt import (
        _forest_builder, bin_features, quantile_bin_edges,
        sharded_hist_args,
    )
    from flinkml_tpu.parallel import DeviceMesh

    rng = np.random.default_rng(0)
    x = rng.uniform(-1, 1, size=(N, D)).astype(np.float32)
    y = (x[:, 0] * x[:, 1] > 0).astype(np.float32)
    w = np.ones(N, dtype=np.float32)
    binned = bin_features(x, quantile_bin_edges(x, BINS))
    mesh = DeviceMesh()
    builder = _forest_builder(
        mesh.mesh, DeviceMesh.DATA_AXIS, D, BINS, DEPTH, TREES, True,
        hist_layout=layout,
    )
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    hist_args = sharded_hist_args(binned, mesh, BINS, layout)
    args = (
        mesh.shard_batch(binned), mesh.shard_batch(y), mesh.shard_batch(w),
        f32(0.0), f32(0.2), f32(1.0), f32(1.0), jax.random.PRNGKey(0),
    ) + hist_args
    feats = np.asarray(builder(*args)[0])       # compile + warm
    t0 = time.perf_counter()
    np.asarray(builder(*args)[2])
    dt = time.perf_counter() - t0
    print(
        f"{layout:8s}: {dt:6.2f}s/forest -> "
        f"{N * TREES / dt / 1e3:9.1f}k row-trees/s",
        flush=True,
    )
    return feats


def main():
    f_seg = run("segment")
    f_cum = run("cumsum")
    same = (f_seg == f_cum).mean()
    print(f"split-feature agreement: {same:.4f}", flush=True)
    assert same > 0.99, "layouts built different forests — timing invalid"


if __name__ == "__main__":
    main()
