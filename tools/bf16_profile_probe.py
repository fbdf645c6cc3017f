"""Device probe: where does bf16's missing 2x go? (VERDICT r4 weak #5)

Round-4 measured the bf16 dense stage at 835M samples/s — 50% of its
1.66G/s byte-bound roofline — while f32 hits 66-71% of its own bound.
BASELINE.md attributes the gap to per-step fixed costs (loop control,
the [d] coefficient-update chain, reduction epilogues) that don't shrink
when the streamed bytes halve; this probe MEASURES that attribution:

1. The product dense trainer at d = 123 (the bench shape), 512, and
   1024, f32 vs bf16. If the bf16/f32 ratio grows toward 2x with d, the
   d=123 gap is the fixed-cost share, not a bf16-path defect.
2. A stream-only kernel (same rotating window + psum, coefficient chain
   removed) at the same shapes — the achievable ceiling for the access
   pattern; the delta to (1) is the per-step update-chain cost.

Output: one ms/step line per (variant, d, dtype) — transcribe into
BASELINE.md's bf16 section.
"""

import time

import numpy as np


N, BS, STEPS = 1_000_000, 262_144, 200


def data(dim, dtype):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(N, dim)).astype(np.float32)
    true_coef = rng.normal(size=dim).astype(np.float32)
    y = (x @ true_coef > 0).astype(np.float32)
    w = np.ones(N, dtype=np.float32)
    return x.astype(dtype), y.astype(dtype), w.astype(dtype)


def run_trainer(dim, dtype_name):
    import jax.numpy as jnp
    from flinkml_tpu.models import _linear_sgd
    from flinkml_tpu.models.logistic_regression import (
        _device_trainer,
        _shard_training_data,
    )
    from flinkml_tpu.parallel import DeviceMesh

    dtype = jnp.bfloat16 if dtype_name == "bf16" else np.float32
    x, y, w = data(dim, dtype)
    mesh = DeviceMesh()
    p = mesh.axis_size()
    xd, yd, wd = _shard_training_data(x, y, w, mesh)
    local_bs = _linear_sgd.align_local_bs(BS, p, xd.shape[0] // p)
    trainer = _device_trainer(mesh.mesh, local_bs, DeviceMesh.DATA_AXIS)
    f = lambda v: jnp.asarray(v, xd.dtype)
    carry0 = (jnp.zeros(xd.shape[1], xd.dtype), jnp.asarray(0, jnp.int32),
              jnp.asarray(jnp.inf, xd.dtype))
    args = (xd, yd, wd, f(0.1), f(0.0), f(0.0), f(0.0))
    np.asarray(trainer(*carry0, *args, jnp.asarray(5, jnp.int32))[0])
    t0 = time.perf_counter()
    coef, steps_out, _ = trainer(*carry0, *args, jnp.asarray(STEPS, jnp.int32))
    np.asarray(coef)
    dt = time.perf_counter() - t0
    assert int(steps_out) == STEPS
    print(f"trainer     d={dim:5d} {dtype_name}: {dt * 1e3 / STEPS:7.3f} "
          f"ms/step -> {local_bs * p * STEPS / dt / 1e6:8.1f}M samples/s",
          flush=True)


def run_stream_only(dim, dtype_name):
    """Ceiling: the same per-step x window read + matvec + psum, with the
    coefficient update chain replaced by a scalar carry."""
    import jax
    import jax.numpy as jnp
    from flinkml_tpu.models import _linear_sgd
    from flinkml_tpu.parallel import DeviceMesh
    from jax.sharding import PartitionSpec as P

    dtype = jnp.bfloat16 if dtype_name == "bf16" else np.float32
    x, _, _ = data(dim, dtype)
    mesh = DeviceMesh()
    p = mesh.axis_size()
    pad = (-x.shape[0]) % p
    if pad:
        x = np.concatenate([x, x[:pad]])
    local_bs = _linear_sgd.align_local_bs(BS, p, x.shape[0] // p)
    probe_vec = jnp.ones((dim,), dtype)

    def per_device(acc, xl, n_steps):
        def body(i, acc):
            xb = _linear_sgd._window(xl, i, local_bs)
            s = jnp.sum((xb @ probe_vec).astype(jnp.float32))
            return acc + jax.lax.psum(s, DeviceMesh.DATA_AXIS)
        return jax.lax.fori_loop(0, n_steps, body, acc)

    fn = jax.jit(jax.shard_map(
        per_device, mesh=mesh.mesh,
        in_specs=(P(), P(DeviceMesh.DATA_AXIS), P()),
        out_specs=P(),
    ))
    xd = mesh.shard_batch(x)
    np.asarray(fn(jnp.float32(0), xd, jnp.asarray(5, jnp.int32)))
    t0 = time.perf_counter()
    np.asarray(fn(jnp.float32(0), xd, jnp.asarray(STEPS, jnp.int32)))
    dt = time.perf_counter() - t0
    print(f"stream-only d={dim:5d} {dtype_name}: {dt * 1e3 / STEPS:7.3f} "
          f"ms/step -> {local_bs * p * STEPS / dt / 1e6:8.1f}M samples/s",
          flush=True)


def main():
    for dim in (123, 512, 1024):
        for dt in ("f32", "bf16"):
            run_trainer(dim, dt)
            run_stream_only(dim, dt)


if __name__ == "__main__":
    main()
