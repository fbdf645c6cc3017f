"""Microbenchmark: gather/scatter bounds-check modes at Criteo shapes.

The sparse LR step is gather/scatter-bound (BASELINE.md round-4
sorted-scatter A/B). Both hot ops run in XLA's default CLIP mode even
though the ELL ids are in-bounds by construction (pack pads with real
column ids); PROMISE_IN_BOUNDS removes the clamp from the hot loop.
Compares one full forward+scatter step (gather coef[ids] -> weighted
reduce -> segment_sum back to [dim]) across the 2x2 of modes.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np


n_rows, nnz, dim, steps = 262_144, 39, 1_000_000, 20
rng = np.random.default_rng(0)
ids2d = rng.integers(0, dim, (n_rows, nnz)).astype(np.int32)
vals2d = rng.normal(size=(n_rows, nnz)).astype(np.float32)
PIB = jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS


def loop(gather_pib: bool, scatter_pib: bool):
    ids_d = jnp.asarray(ids2d)
    vals_d = jnp.asarray(vals2d)
    flat_ids = ids_d.reshape(-1)

    @jax.jit
    def run(coef):
        def body(i, c):
            if gather_pib:
                g = c.at[ids_d].get(mode=PIB)
            else:
                g = c[ids_d]
            dot = jnp.sum(vals_d * g, axis=1)
            contrib = (vals_d * dot[:, None]).reshape(-1)
            grad = jax.ops.segment_sum(
                contrib, flat_ids, num_segments=dim,
                mode=PIB if scatter_pib else None,
            )
            return c - 1e-9 * grad

        return jax.lax.fori_loop(0, steps, body, coef)

    return run


def main():
    coef = jnp.zeros(dim, jnp.float32)
    for name, gp, sp in [
        ("clip gather, clip scatter (today)", False, False),
        ("PIB  gather, clip scatter       ", True, False),
        ("clip gather, PIB  scatter       ", False, True),
        ("PIB  gather, PIB  scatter       ", True, True),
    ]:
        fn = loop(gp, sp)
        np.asarray(fn(coef))  # compile + warm
        t0 = time.perf_counter()
        np.asarray(fn(coef))
        dt = time.perf_counter() - t0
        print(f"{name}: {dt*1e3/steps:7.2f} ms/step -> "
              f"{n_rows*steps/dt/1e6:6.2f}M samples/s", flush=True)


if __name__ == "__main__":
    main()
