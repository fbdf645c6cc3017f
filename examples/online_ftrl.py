"""Online learning: FTRL-proximal logistic regression over an unbounded
stream, warm-started from an offline model — the reference's
OnlineLogisticRegression workflow (continuous mini-batch updates with a
model version per update).

Runs on TPU, or on a virtual CPU mesh with:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/online_ftrl.py
"""

# Runnable standalone from any cwd: put the repo root on sys.path when
# flinkml_tpu isn't already importable (pip-installed or PYTHONPATH set).
import os as _os
import sys as _sys

try:
    import flinkml_tpu  # noqa: F401
except ImportError:
    _sys.path.insert(
        0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    )

import numpy as np

from flinkml_tpu.models import LogisticRegression, OnlineLogisticRegression
from flinkml_tpu.table import Table

rng = np.random.default_rng(1)
d = 16
true_coef = rng.normal(size=d)


def make_batch(n):
    x = rng.normal(size=(n, d))
    y = (x @ true_coef + 0.2 * rng.normal(size=n) > 0).astype(np.float64)
    return Table({"features": x, "label": y})


# --- Offline warm start ---------------------------------------------------
offline_table = make_batch(2000)
offline = (
    LogisticRegression().set_seed(0).set_max_iter(100)
    .set_global_batch_size(2000).fit(offline_table)
)

# --- Online phase: one FTRL update per arriving batch ---------------------
online = (
    OnlineLogisticRegression()
    .set_alpha(0.1)
    .set_beta(1.0)
    .set_reg(0.001)
    .set_elastic_net(0.5)
    .set_initial_model_data(*offline.get_model_data())
)
stream = (make_batch(256) for _ in range(50))  # a live one-shot stream
model = online.fit_stream(stream)
print("model version after stream:", model.model_version)

# --- The refreshed model still predicts the concept -----------------------
test = make_batch(1000)
(out,) = model.transform(test)
acc = float(np.mean(out["prediction"] == test["label"]))
print(f"online-updated accuracy: {acc:.3f}")
assert acc > 0.9
