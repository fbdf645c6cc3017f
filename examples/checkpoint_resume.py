"""Fault-tolerant training: chunked device-loop checkpointing + exact resume.

The trainer runs K epochs per device dispatch and snapshots the carry
(coefficient, epoch, loss) between dispatches; a crash loses at most one
chunk, and the resumed run re-enters the SAME compiled executable, so the
final model is bit-identical to an uninterrupted run.

Runs on TPU, or on a virtual CPU mesh with:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/checkpoint_resume.py
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

import tempfile

import numpy as np

from flinkml_tpu.iteration import CheckpointManager
from flinkml_tpu.models.logistic_regression import train_logistic_regression
from flinkml_tpu.parallel import DeviceMesh

rng = np.random.default_rng(0)
n, d = 4096, 32
x = rng.normal(size=(n, d)).astype(np.float32)
y = (x @ rng.normal(size=d) > 0).astype(np.float32)
w = np.ones(n, dtype=np.float32)

mesh = DeviceMesh()
hyper = dict(
    mesh=mesh, max_iter=60, learning_rate=0.5, global_batch_size=n,
    reg=0.0, tol=0.0, seed=42,
)

# --- Golden run: no failures, whole loop in one dispatch ------------------
golden = train_logistic_regression(x, y, w, **hyper)

with tempfile.TemporaryDirectory() as td:
    mgr = CheckpointManager(td)

    # --- "Crash" after 24 epochs (checkpoint every 12) --------------------
    train_logistic_regression(
        x, y, w, **{**hyper, "max_iter": 24},
        checkpoint_manager=mgr, checkpoint_interval=12,
    )
    print("checkpoints on disk:", mgr.all_epochs())  # [12, 24]

    # --- Resume: restores the epoch-24 carry, finishes to 60 --------------
    resumed = train_logistic_regression(
        x, y, w, **hyper,
        checkpoint_manager=mgr, checkpoint_interval=12, resume=True,
    )

np.testing.assert_allclose(resumed, golden, rtol=1e-12)
print("resumed coefficients are exactly the uninterrupted result")
