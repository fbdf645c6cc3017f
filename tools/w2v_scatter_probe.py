"""Device A/B: Word2Vec embedding-gradient accumulation formulations.

The roofline audit put the SGNS stage at 5% of its ~40M pairs/s bound
and attributed it to the per-step row scatters (49k rows x 512 B
payloads into [vocab, dim]) sort-lowering. The scatter here is
matmul-shaped (one_hot(ids)^T @ grads is a true matrix-matrix product
at d=128), but a materialized one-hot costs bs x vocab x 4 B per table
per step — only an XLA-fused one-hot wins. This probe measures, at the
bench shape (vocab 32k, d=128, bs 8192, 5 negatives):

  scatter  — .at[ids].add(rows) (the product trainer's formulation)
  onehot   — jnp.einsum('bv,bd->vd', one_hot(ids), rows): does XLA fuse
             the iota-compare into the dot operand or materialize 1 GB?
  segsum   — jax.ops.segment_sum over rows (same scatter class, checks
             whether the lowering differs from .at[].add)

Prints ms/step per formulation; a winner >=2x faster than `scatter`
justifies a gated product variant.
"""

import time

import numpy as np


VOCAB, DIM, BS, N_NEG, STEPS = 32_768, 128, 8_192, 5, 100


def main():
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    n_rows = BS * (1 + N_NEG)   # ctx + negatives (the u-table update)
    ids = jnp.asarray(rng.integers(0, VOCAB, size=n_rows).astype(np.int32))
    rows = jnp.asarray(rng.normal(size=(n_rows, DIM)).astype(np.float32))

    def loop(accum_fn):
        @jax.jit
        def run(rows):
            def body(i, acc):
                return acc + accum_fn(rows * (1.0 + 1e-6 * i))[0, 0]
            return jax.lax.fori_loop(0, STEPS, body, jnp.float32(0))
        return run

    variants = {
        "scatter": lambda r: jnp.zeros((VOCAB, DIM)).at[ids].add(r),
        "onehot": lambda r: jnp.einsum(
            "bv,bd->vd",
            jax.nn.one_hot(ids, VOCAB, dtype=jnp.float32), r,
        ),
        "segsum": lambda r: jax.ops.segment_sum(
            r, ids, num_segments=VOCAB
        ),
    }
    for name, fn in variants.items():
        run = loop(fn)
        try:
            np.asarray(run(rows))       # compile + warm
            t0 = time.perf_counter()
            np.asarray(run(rows))
            dt = time.perf_counter() - t0
            print(f"{name:8s}: {dt * 1e3 / STEPS:8.3f} ms/step", flush=True)
        except Exception as e:  # noqa: BLE001 — e.g. OOM on materialized OH
            print(f"{name:8s}: FAILED ({type(e).__name__}: {e})", flush=True)


if __name__ == "__main__":
    main()
