"""``driver: fit_sparse_dp`` — ``fit_sparse``'s cell on a table that only
several chips hold: whole ``LogisticRegression().fit(Table)`` calls on ONE
``Table`` whose ``CsrColumn`` the program shards over every chip JAX sees
(``DeviceMesh()``: data-parallel workers, one all-reduce of the gradient
a step), back to back; a new fit starts while the window is open and the
one in flight always finishes.

Set-up is ``fit_sparse``'s and the fit call and the window ``fit``'s
(imported, not edited); what several chips need is here. The checks:
``fit_sparse``'s three (finite, every timed fit equal to set-up's to the
bit, no ``SparseVector`` built), and

- every chip holds ``ceil(rows / workers)`` rows of every table-long
  array alive after the window and no chip another's (``jax.live_arrays``:
  the placement the ``Table`` keeps, by its addressable shards);
- the program's own count of a fit's workers (``trainer.mesh_devices`` a
  fit) is the number of chips; a program from before the counter prints
  the line with the chips' number and says so;
- the last timed fit's coefficients against NumPy float64 SGD replayed
  over the SHARDED order as the configuration's words define it
  (``reference/sparse_linear_dp.py``): step ``k``'s batch is the union of
  the workers' ``k``-th local windows.

The workers are the chips the harness checked (the cell's ``chips``); a
rehearsal takes the devices it finds (eight under the tests) and
overrides rows, batch and steps only. ``setup`` prints the host's peak
resident set: 45.8 M rows are 14.3 GB of cells on the host before the
first fit packs and stages them.
"""

from __future__ import annotations

import json
import resource

import numpy as np

from benchmark.drivers import fit as dense
from benchmark.drivers import fit_sparse as sparse
from benchmark.reference import sparse_linear_dp as reference

window = dense.window

#: Threads of the reference's replay: the cell's host has thirty cores.
_REPLAY_THREADS = 16


def setup(ctx):
    import jax

    s = sparse.setup(ctx)
    s.workers = jax.device_count()
    # ru_maxrss is KiB on Linux: the generator's arrays, the column's
    # check, the plan, the permutation and the staging rounds are behind.
    print(json.dumps({
        "phase": "host", "workers": s.workers,
        "host_peak_rss_bytes":
            1024 * resource.getrusage(resource.RUSAGE_SELF).ru_maxrss}),
        flush=True)
    return s


def _chips_off_their_share(rows: int, workers: int) -> int:
    """Chips whose shard of some table-long live array is not
    ``ceil(rows / workers)`` rows, or that hold none."""
    import jax

    n_local, _, _ = reference.shard_layout(rows, workers, 1)
    held = {d: set() for d in jax.devices()}
    for a in jax.live_arrays():
        if a.ndim and a.shape[0] == workers * n_local:
            for shard in a.addressable_shards:
                held[shard.device].add(shard.data.shape[0])
    return sum(1 for sizes in held.values() if sizes != {n_local})


def check(ctx, s, result, counters):
    limits = ctx.size("limits")
    out = []
    finite = all(np.isfinite(c).all() and c.shape == (s.dim,) for c in s.coefs)
    out.append({"what": "fits with a non-finite coefficient",
                "value": 0 if finite else 1, "limit": 0})
    spread = max(float(np.max(np.abs(c - s.coefs[0]))) for c in s.coefs[1:])
    out.append({"what": f"coefficients of the {len(s.coefs) - 1} timed fit(s), widest "
                        "difference from set-up's fit (same seed, same table, "
                        "same mesh)",
                "value": spread if finite else None, "limit": 0.0})
    out.append({"what": "SparseVector rows built from the CsrColumn inside the "
                        "window (table.csr_rows_materialized)",
                "value": counters.get("table.csr_rows_materialized"), "limit": 0})
    n_local, local, windows = reference.shard_layout(s.rows, s.workers, s.batch)
    out.append({"what": f"chips of {s.workers} whose shard of a kept table-long "
                        f"array is not {n_local} rows",
                "value": _chips_off_their_share(s.rows, s.workers), "limit": 0})
    fits = len(result["unit_walls_s"])
    counted = counters.get("trainer.mesh_devices")
    out.append({"what": "workers of a timed fit (trainer.mesh_devices a fit) off "
                        f"the {s.workers} chips"
                        + ("" if counted is not None else
                           ": the program has no such count"),
                "value": 0.0 if counted is None
                else abs(counted / fits - s.workers), "limit": 0.0})
    # The timed fit itself, replayed from the configuration's words.
    order = reference.seeded_order(ctx.seed % (1 << 31), s.rows)
    want = reference.minibatch_sgd(
        s.indices.reshape(s.rows, s.nnz), s.values.reshape(s.rows, s.nnz),
        s.dim, s.y, s.max_iter, float(ctx.cell["learning_rate"]), s.batch,
        order, s.workers, threads=_REPLAY_THREADS)
    gap = float(np.max(np.abs(s.coefs[-1] - want))) if finite else None
    out.append({"what": f"last timed fit ({s.rows} rows of {s.nnz} cells over "
                        f"{s.workers} workers, {n_local} rows and {windows} windows "
                        f"of {local} a worker, {s.max_iter} steps): widest "
                        f"coefficient gap to float64 SGD over the sharded order "
                        f"(largest |coefficient| {float(np.max(np.abs(want))):.4f}, "
                        f"{int(np.count_nonzero(want))} columns touched)",
                "value": gap, "limit": limits["coef_gap"]})
    return out
