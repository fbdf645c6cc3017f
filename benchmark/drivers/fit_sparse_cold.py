"""``driver: fit_sparse_cold`` — ``fit_sparse``'s cell on a table the
program has never seen: before every timed fit a NEW ``Table`` is built
over the same ``CsrColumn`` and label arrays and the one before is
dropped, outside the timed unit (a ``Table`` around a validated column
costs microseconds). Whatever a fit keeps with its table, every timed
fit here starts without: a day's new click logs, fitted once.

Set-up and the checks are ``fit_sparse``'s, the fit call ``fit``'s
(imported, not edited); the window differs from ``fit.window`` by the
one line that builds the table.
"""

from __future__ import annotations

import time

from benchmark.drivers import fit as dense
from benchmark.drivers import fit_sparse as sparse

setup = sparse.setup
check = sparse.check


def window(ctx, s):
    from flinkml_tpu.table import Table

    walls = []
    t_open = time.perf_counter()
    while True:
        s.table = Table({"features": s.table.csr_column("features"),
                         "label": s.y})
        t0 = time.perf_counter()
        with ctx.unit("fit", fits=1, steps=s.max_iter,
                      samples=s.max_iter * s.batch):
            s.coefs.append(dense._fit(ctx, s.table, s.batch, s.max_iter))
        now = time.perf_counter()
        walls.append(now - t0)
        if now - t_open >= ctx.seconds:
            break
    return {"work": len(walls) * s.max_iter * s.batch, "wall_s": now - t_open,
            "attempted": len(walls), "failed": 0, "unit_walls_s": walls}
