"""End-to-end example: CSV → pipeline (assemble + scale + LR) → evaluate →
save/load. Runs on TPU, or on a virtual CPU mesh with:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_logistic_regression.py
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

import os
import tempfile

import numpy as np

from flinkml_tpu.io import read_csv_table
from flinkml_tpu.models import (
    BinaryClassificationEvaluator,
    LogisticRegression,
    StandardScaler,
    VectorAssembler,
)
from flinkml_tpu.pipeline import Pipeline, PipelineModel
from flinkml_tpu.table import Table

# --- Synthesize a CSV (stand-in for your data file) ----------------------
rng = np.random.default_rng(0)
n, d = 5000, 12
x = rng.normal(size=(n, d))
y = (x @ rng.normal(size=d) + 0.3 * rng.normal(size=n) > 0).astype(int)
header = ",".join([f"f{i}" for i in range(d)] + ["label"])
rows = "\n".join(
    ",".join(f"{v:.6g}" for v in row) + f",{lab}" for row, lab in zip(x, y)
)
csv_path = os.path.join(tempfile.gettempdir(), "example_train.csv")
with open(csv_path, "w") as f:
    f.write(header + "\n" + rows + "\n")

# --- Ingest (native multithreaded parser) --------------------------------
table = read_csv_table(csv_path)

# --- Pipeline: assemble feature columns → standardize → train ------------
pipe = Pipeline([
    VectorAssembler().set_input_cols([f"f{i}" for i in range(d)])
                     .set(VectorAssembler.OUTPUT_COL, "input"),
    StandardScaler(),
    LogisticRegression().set_features_col("output").set_label_col("label")
                        .set_max_iter(100).set_learning_rate(0.5)
                        .set_global_batch_size(4096).set_reg(0.01)
                        .set_seed(42),
])
model = pipe.fit(table)

# --- Score + evaluate ----------------------------------------------------
(scored,) = model.transform(table)
(metrics,) = (
    BinaryClassificationEvaluator()
    .set(BinaryClassificationEvaluator.METRICS_NAMES,
         ["areaUnderROC", "accuracy"])
    .transform(scored)
)
print("AUC:", float(metrics.column("areaUnderROC")[0]))
print("accuracy:", float(metrics.column("accuracy")[0]))

# --- Persist and reload --------------------------------------------------
with tempfile.TemporaryDirectory() as td:
    path = os.path.join(td, "model")
    model.save(path)
    reloaded = PipelineModel.load(path)
    (rescored,) = reloaded.transform(table)
    assert np.array_equal(
        np.asarray(rescored.column("prediction")),
        np.asarray(scored.column("prediction")),
    )
    print("save/load round-trip OK")
