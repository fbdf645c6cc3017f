"""KMeans example: k-means++ init, whole Lloyd loop in one XLA program,
save/load, cluster-quality check against sklearn.

Runs on TPU, or on a virtual CPU mesh with:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_kmeans.py
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

from flinkml_tpu.models import KMeans, KMeansModel
from flinkml_tpu.table import Table

# --- Three well-separated blobs ------------------------------------------
rng = np.random.default_rng(7)
centers = np.array([[0.0, 0.0], [6.0, 6.0], [-6.0, 5.0]])
x = np.concatenate([c + rng.normal(scale=0.7, size=(400, 2)) for c in centers])
table = Table({"features": x})

# --- Fit: the entire Lloyd iteration is ONE device dispatch --------------
kmeans = (
    KMeans()
    .set_k(3)
    .set_max_iter(30)
    .set_seed(0)
    .set_init_mode("k-means++")
)
model = kmeans.fit(table)

(out,) = model.transform(table)
assign = np.asarray(out["prediction"])
print("cluster sizes:", np.bincount(assign.astype(int)))

# Each learned centroid should sit on one true blob center.
learned = np.sort(model.centroids, axis=0)
print("learned centroids (sorted):\n", np.round(learned, 2))

# --- sklearn agreement (adjusted Rand index = 1.0 on separated blobs) -----
try:
    from sklearn.cluster import KMeans as SkKMeans
    from sklearn.metrics import adjusted_rand_score

    sk = SkKMeans(n_clusters=3, n_init=5, random_state=0).fit(x)
    ari = adjusted_rand_score(sk.labels_, assign)
    print(f"adjusted Rand vs sklearn: {ari:.3f}")
except ImportError:
    pass

# --- Persist and reload --------------------------------------------------
with tempfile.TemporaryDirectory() as td:
    path = os.path.join(td, "kmeans_model")
    model.save(path)
    reloaded = KMeansModel.load(path)
    (again,) = reloaded.transform(table)
    assert np.array_equal(np.asarray(again["prediction"]), assign)
    print("save/load round-trip OK")
