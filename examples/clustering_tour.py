"""Five clustering algorithms on two datasets that tell them apart:
blobs (everyone succeeds) and concentric rings (only affinity-based
clustering can).

Run: PYTHONPATH=. JAX_PLATFORMS=cpu python examples/clustering_tour.py
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
from sklearn.metrics import adjusted_rand_score

from flinkml_tpu.models import (
    AgglomerativeClustering,
    BisectingKMeans,
    GaussianMixture,
    KMeans,
    PowerIterationClustering,
)
from flinkml_tpu.table import Table

rng = np.random.default_rng(0)

# -- dataset 1: three gaussian blobs ----------------------------------------
x_blobs = np.concatenate([
    rng.normal(size=(80, 2)) * 0.5 + c for c in ([0, 0], [5, 0], [0, 5])
])
y_blobs = np.repeat([0, 1, 2], 80)
t_blobs = Table({"features": x_blobs})

results = {}
(km,) = KMeans().set_k(3).set_init_mode("k-means++").set_seed(0).fit(
    t_blobs).transform(t_blobs)
results["KMeans"] = adjusted_rand_score(y_blobs, km["prediction"])
(bk,) = BisectingKMeans().set_k(3).set_seed(0).fit(t_blobs).transform(t_blobs)
results["BisectingKMeans"] = adjusted_rand_score(y_blobs, bk["prediction"])
(gm,) = GaussianMixture().set_k(3).set_seed(0).set_max_iter(80).fit(
    t_blobs).transform(t_blobs)
results["GaussianMixture"] = adjusted_rand_score(y_blobs, gm["prediction"])
(ag,) = AgglomerativeClustering().set_num_clusters(3).transform(t_blobs)
results["Agglomerative"] = adjusted_rand_score(y_blobs, ag["prediction"])
print("blobs:", {k: round(v, 3) for k, v in results.items()})

# -- dataset 2: concentric rings --------------------------------------------
theta = rng.uniform(0, 2 * np.pi, 200)
r = np.concatenate([np.full(100, 1.0), np.full(100, 4.0)])
r += 0.1 * rng.normal(size=200)
x_rings = np.stack([r * np.cos(theta), r * np.sin(theta)], axis=1)
y_rings = np.repeat([0, 1], 100)

(km2,) = KMeans().set_k(2).set_seed(0).fit(
    Table({"features": x_rings})).transform(Table({"features": x_rings}))
km2_ari = adjusted_rand_score(y_rings, km2["prediction"])

# kNN affinity graph for PIC.
d2 = ((x_rings[:, None] - x_rings[None]) ** 2).sum(-1)
np.fill_diagonal(d2, np.inf)
knn = np.argsort(d2, axis=1)[:, :8]
src = np.repeat(np.arange(200), 8)
dst = knn.ravel()
edges = Table({"src": src, "dst": dst,
               "w": np.exp(-d2[src, dst] / 0.5)})
(pic,) = (
    PowerIterationClustering().set_k(2).set_max_iter(50)
    .set_weight_col("w").set_seed(0).transform(edges)
)
order = np.argsort(pic["id"])
pic_ari = adjusted_rand_score(y_rings, pic["prediction"][order])
print(f"rings: KMeans ARI={km2_ari:.3f}  PIC ARI={pic_ari:.3f}  "
      "(affinity clustering handles non-convex shapes)")
