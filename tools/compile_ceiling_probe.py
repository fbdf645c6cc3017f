"""Characterize the d >= 512 compile-time ceiling.

Times COMPILATION (not execution) of the exact product programs the bench
could not fit at MNIST-784 shapes — the whole-loop KMeans trainer and the
dense-LR trainer — across widths, on the current backend. Run twice:

    JAX_PLATFORMS=cpu python tools/compile_ceiling_probe.py   # XLA:CPU
    python tools/compile_ceiling_probe.py                     # device

If the CPU curve stays flat while the device curve blows up, the cost is
in the TPU backend (Mosaic/XLA:TPU lowering), not in the program
structure; if both blow up, the program shape itself is the
problem and needs restructuring (e.g. shape bucketing).

Each (workload, d) compile runs in a CHILD process with JAX's persistent
compilation cache off (``JAX_COMPILATION_CACHE_DIR`` removed from its
environment) so times are cold and one hang cannot kill the sweep. The
parent never touches JAX, so each child has the chip to itself.
"""

import json
import os
import subprocess
import sys
import time

_INNER = "_COMPILE_PROBE_INNER"


def _inner(spec: str) -> None:
    kind, d_str = spec.split(":")
    d = int(d_str)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flinkml_tpu.parallel import DeviceMesh

    mesh = DeviceMesh()
    t0 = time.perf_counter()
    if kind == "kmeans":
        from flinkml_tpu.models.kmeans import _kmeans_trainer, _place_rows

        n, k = 65_536, 64
        x = np.zeros((n, d), np.float32)
        placed = _place_rows(x, mesh)
        trainer = _kmeans_trainer(mesh.mesh, k, DeviceMesh.DATA_AXIS)
        lowered = trainer.lower(
            *placed, jnp.zeros((k, d), jnp.float32),
            jnp.asarray(3, jnp.int32),
        )
        t_lower = time.perf_counter() - t0
        t1 = time.perf_counter()
        lowered.compile()
        t_compile = time.perf_counter() - t1
    else:  # dense LR
        from flinkml_tpu.models import _linear_sgd
        from flinkml_tpu.models.logistic_regression import _device_trainer

        n = 65_536
        p = mesh.axis_size()
        local_bs = _linear_sgd.align_local_bs(8_192, p, n // p)
        trainer = _device_trainer(mesh.mesh, local_bs, DeviceMesh.DATA_AXIS)
        xd = mesh.shard_batch(np.zeros((n, d), np.float32))
        yd = mesh.shard_batch(np.zeros(n, np.float32))
        wd = mesh.shard_batch(np.ones(n, np.float32))
        f32 = lambda v: jnp.asarray(v, jnp.float32)
        lowered = trainer.lower(
            jnp.zeros(d, jnp.float32), jnp.asarray(0, jnp.int32),
            jnp.asarray(jnp.inf, jnp.float32),
            xd, yd, wd, f32(0.1), f32(0.0), f32(0.0), f32(0.0),
            jnp.asarray(10, jnp.int32),
        )
        t_lower = time.perf_counter() - t0
        t1 = time.perf_counter()
        lowered.compile()
        t_compile = time.perf_counter() - t1
    print(json.dumps({
        "kind": kind, "d": d, "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "platform": jax.devices()[0].platform,
    }))


def main() -> None:
    per_case_timeout = float(os.environ.get("COMPILE_PROBE_TIMEOUT", "900"))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    cases = [
        f"{kind}:{d}"
        for kind in ("kmeans", "dense")
        for d in (128, 256, 512, 784)
    ]
    for spec in cases:
        t0 = time.perf_counter()
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                env={**env, _INNER: spec},
                timeout=per_case_timeout,
                stdout=subprocess.PIPE, text=True,
            )
            out = proc.stdout.strip().splitlines()
            print(out[-1] if out else f"{spec}: rc={proc.returncode}",
                  flush=True)
        except subprocess.TimeoutExpired:
            print(json.dumps({
                "case": spec, "timeout_s": per_case_timeout,
                "elapsed": round(time.perf_counter() - t0, 1),
            }), flush=True)


if __name__ == "__main__":
    if os.environ.get(_INNER):
        _inner(os.environ[_INNER])
    else:
        main()
