"""Device A/B: sparse gradient layouts at the Criteo profile.

Runs the PRODUCT bucketed trainer (`_sparse_trainer_bucketed`, the exact
program `LinearModel.fit` and bench's sparse stage dispatch) at the bench
sparse shape (262k rows x 39 nnz, dim = 1e6) once per layout:

  unsorted — per-step segment_sum (round-4 measured winner: 69.1 ms/step)
  sorted   — round-3 pack-sorted + indices_are_sorted (90.9 ms/step)
  cumsum   — round-5 sort-free layout: pack-time column-sorted cells with
             values + row ids; step = small mult-gather, one running sum,
             boundary differences, <=max_d sorted unique adds.

Prints ms/step + samples/s per layout; the winner sets the product
default (the measured-defaults discipline of BASELINE.md). A second
cumsum run uses Zipf(1.2) column ids — the realistic Criteo frequency
profile — to check the layout's sensitivity to run-length distribution
(uniform ids produce ~cells distinct runs; Zipf produces hot runs).
"""

import time

import numpy as np


N, NNZ, DIM, STEPS = 262_144, 39, 1_000_000, 50


def make_csr(col_dist, seed=0):
    from bench import make_criteo_csr

    indptr, indices, values, y, w = make_criteo_csr(N, DIM, NNZ, seed)
    if col_dist == "zipf":  # the Criteo-like frequency skew
        rng = np.random.default_rng(seed + 1)
        indices = np.minimum(
            rng.zipf(1.2, size=N * NNZ) - 1, DIM - 1
        ).astype(np.int32)
    return indptr, indices, values, y, w


def run(layout, col_dist):
    import jax.numpy as jnp
    from flinkml_tpu.models import _linear_sgd
    from flinkml_tpu.parallel import DeviceMesh

    indptr, indices, values, y, w = make_csr(col_dist)
    mesh = DeviceMesh()
    t0 = time.perf_counter()
    data_args, local_bss = _linear_sgd.prepare_sparse_buckets(
        indptr, indices, values, DIM, y, w, mesh, N, seed=0, layout=layout,
    )
    pack_s = time.perf_counter() - t0
    trainer = _linear_sgd._sparse_trainer_bucketed(
        mesh.mesh, "logistic", local_bss, DeviceMesh.DATA_AXIS, DIM, layout,
    )
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    carry0 = (
        jnp.zeros(DIM, jnp.float32),
        jnp.asarray(0, jnp.int32),
        jnp.asarray(jnp.inf, jnp.float32),
    )
    hy = (f32(0.1), f32(0.0), f32(0.0), f32(0.0))
    np.asarray(trainer(*carry0, *data_args, *hy,
                       jnp.asarray(3, jnp.int32))[0])  # compile + warm
    t0 = time.perf_counter()
    coef, steps_out, _ = trainer(
        *carry0, *data_args, *hy, jnp.asarray(STEPS, jnp.int32)
    )
    np.asarray(coef)
    dt = time.perf_counter() - t0
    assert int(steps_out) == STEPS, int(steps_out)
    bs = sum(local_bss) * mesh.axis_size()
    print(
        f"{layout:9s} {col_dist:8s}: {dt * 1e3 / STEPS:8.2f} ms/step  "
        f"-> {bs * STEPS / dt / 1e6:8.2f}M samples/s  "
        f"(pack {pack_s:.1f}s)",
        flush=True,
    )


def main():
    for layout in ("unsorted", "cumsum", "sorted"):
        run(layout, "uniform")
    run("cumsum", "zipf")
    run("unsorted", "zipf")


if __name__ == "__main__":
    main()
