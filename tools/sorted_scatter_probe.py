"""Microbenchmark: per-step sort cost of the sparse gradient scatter.

Compares segment_sum at Criteo shapes ([1e7] cells -> [1e6] segments):
  A) unsorted ids (the current trainer: XLA sorts every step)
  B) pre-sorted ids + indices_are_sorted=True (sort paid once at pack)
  C) pre-sorted ids WITHOUT the flag (is the flag or the order what wins?)
"""

import time

import jax
import jax.numpy as jnp
import numpy as np


n_cells, dim, steps = 262_144 * 39, 1_000_000, 20
rng = np.random.default_rng(0)
ids = rng.integers(0, dim, n_cells).astype(np.int32)
vals = rng.normal(size=n_cells).astype(np.float32)
order = np.argsort(ids, kind="stable")
ids_sorted = ids[order]
vals_sorted = vals[order]


def loop(ids_dev, flag):
    @jax.jit
    def run(v):
        def body(i, acc):
            seg = jax.ops.segment_sum(
                v * (1.0 + 1e-6 * i), ids_dev, num_segments=dim,
                indices_are_sorted=flag,
            )
            return acc + seg[0]
        return jax.lax.fori_loop(0, steps, body, jnp.float32(0))
    return run


def main():
    for name, i_np, v_np, flag in [
        ("unsorted         ", ids, vals, False),
        ("sorted+flag      ", ids_sorted, vals_sorted, True),
        ("sorted, no flag  ", ids_sorted, vals_sorted, False),
    ]:
        i_dev = jnp.asarray(i_np)
        v_dev = jnp.asarray(v_np)
        fn = loop(i_dev, flag)
        np.asarray(fn(v_dev))          # compile + warm
        t0 = time.perf_counter()
        np.asarray(fn(v_dev))
        dt = time.perf_counter() - t0
        sps = 262_144 * steps / dt
        print(f"{name}: {dt*1e3/steps:7.2f} ms/step  -> "
              f"{sps/1e6:8.2f}M samples/s", flush=True)


if __name__ == "__main__":
    main()
