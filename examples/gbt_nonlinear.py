"""Gradient-boosted trees on a nonlinear task a linear model cannot fit.

Run: PYTHONPATH=. python examples/gbt_nonlinear.py
(CPU mesh: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8)
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

from flinkml_tpu.models import (
    BinaryClassificationEvaluator,
    GBTClassifier,
    LogisticRegression,
    RandomSplitter,
)
from flinkml_tpu.table import Table

rng = np.random.default_rng(0)
n = 4000
x = rng.uniform(-2, 2, size=(n, 6))
# XOR-of-signs interaction + a sinusoid: zero linear signal.
logits = 3.0 * (x[:, 0] * x[:, 1] > 0) - 1.5 + np.sin(3 * x[:, 2])
y = (rng.uniform(size=n) < 1 / (1 + np.exp(-logits))).astype(np.float64)
data = Table({"features": x, "label": y})
train, test = RandomSplitter().set_weights([0.8, 0.2]).set_seed(0).transform(data)

gbt = (
    GBTClassifier().set_num_trees(40).set_max_depth(4)
    .set_learning_rate(0.2).set_seed(0)
)
model = gbt.fit(train)
(pred,) = model.transform(test)

lr = (
    LogisticRegression().set_max_iter(60).set_global_batch_size(1024)
    .set_learning_rate(1.0).set_seed(0)
)
(lr_pred,) = lr.fit(train).transform(test)

ev = BinaryClassificationEvaluator().set_metrics_names(["areaUnderROC"])
(gbt_auc,) = ev.transform(pred)
(lr_auc,) = ev.transform(lr_pred)
print(f"GBT holdout AUC: {gbt_auc['areaUnderROC'][0]:.3f}   "
      f"(linear baseline: {lr_auc['areaUnderROC'][0]:.3f})")
