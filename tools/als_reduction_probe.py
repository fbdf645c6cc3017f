"""Device A/B: the STREAMED ALS fit's normal-equation reductions at the
bench shape. It decides that formulation's reduction only: ``ALS.fit(Table)``
solves by target block (``models/_als_blocked.py``, PR 38), forms no
per-rating outer product and reads no gate.

The roofline audit (BASELINE.md "rooflines") measured the ALS stage at
1.4% of its streaming bound — the sort-based ``segment_sum`` dragging a
4 KB-per-rating payload through a sort every chunk. The ``cumsum``
reduction sorts the COO by target once at pack time and reduces at
static run boundaries (streaming passes + a runs-sized sorted scatter).

Runs the bench ALS stage (16k x 16k, 2M ratings, rank 32, 10 iters)
through ``models.als.coo_fit`` (the streamed formulation over a COO in
RAM) once per layout; the winner sets the FLINKML_TPU_ALS_REDUCTION
default.
"""

import os
import time

import numpy as np


N_USERS, N_ITEMS, NNZ, RANK, ITERS = 16_384, 16_384, 1 << 21, 32, 10


def run(layout):
    from flinkml_tpu.models.als import coo_fit

    os.environ["FLINKML_TPU_ALS_REDUCTION"] = layout
    rng = np.random.default_rng(0)
    coo = (rng.integers(0, N_USERS, size=NNZ).astype(np.int32),
           rng.integers(0, N_ITEMS, size=NNZ).astype(np.int32),
           rng.uniform(1, 5, size=NNZ).astype(np.float32), N_USERS, N_ITEMS)
    coo_fit(*coo, rank=RANK, max_iter=1, reg=0.1)  # warm
    t0 = time.perf_counter()
    user_f, _ = coo_fit(*coo, rank=RANK, max_iter=ITERS, reg=0.1)
    dt = time.perf_counter() - t0
    print(
        f"{layout:8s}: {dt:6.2f}s -> "
        f"{NNZ * 2 * ITERS / dt / 1e6:8.2f}M rating-visits/s",
        flush=True,
    )
    return user_f


def main():
    u_seg = run("segment")
    u_cum = run("cumsum")
    diff = float(np.abs(u_seg - u_cum).max())
    print(f"factor max |diff|: {diff:.2e}", flush=True)
    assert diff < 1e-3, "layouts diverged — timing invalid"


if __name__ == "__main__":
    main()
