"""The distributed primitives, used directly: mesh, AllReduce, broadcast,
keyed aggregation, mapPartition, host barrier — the building blocks every
estimator trains through (SURVEY.md §2.5's checklist), exposed for writing
custom distributed algorithms.

Runs on TPU, or on a virtual CPU mesh with:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/parallel_primitives.py
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

from flinkml_tpu.parallel import DeviceMesh, host_barrier
from flinkml_tpu.parallel.broadcast_utils import (
    get_broadcast_variable,
    with_broadcast,
)
from flinkml_tpu.parallel.collectives import (
    all_reduce_sum,
    keyed_aggregate,
    map_partition,
)

mesh = DeviceMesh()  # 1-D "data" axis over every device
P = mesh.axis_size()
print(f"mesh: {P} devices on axis '{mesh.DATA_AXIS}'")

# --- AllReduce: per-device partial sums -> identical global sum -----------
# (replaces the reference's 3-hop chunked shuffle, AllReduceImpl.java:52)
parts = np.arange(P * 4, dtype=np.float64).reshape(P, 4)
total = np.asarray(all_reduce_sum(mesh, mesh.shard_batch(parts)))
np.testing.assert_array_equal(total, parts.sum(axis=0))
print("all_reduce_sum:", total)

# --- Broadcast variables: replicate a model to every device ---------------
# (replaces BroadcastUtils.withBroadcastStream / BroadcastContext; inside
# the function the variable is read by name, the reference's
# getBroadcastVariable idiom)
rows = np.arange(P * 8, dtype=np.float64).reshape(P * 8, 1)


def scorer(x_batch):
    model = get_broadcast_variable("model")
    return x_batch * model["bias"]


scored = with_broadcast(
    scorer, (rows,),
    broadcast_variables={"model": {"coef": np.ones(4), "bias": np.array(2.0)}},
    mesh=mesh,
)
np.testing.assert_array_equal(np.asarray(scored), rows * 2.0)
print("with_broadcast: ok")

# --- Keyed aggregation: segment-sum + psum (the keyBy + reduce analog) ----
values = np.ones((P * 8, 2))
keys = np.tile(np.arange(4), P * 2)
sums = np.asarray(keyed_aggregate(
    mesh, mesh.shard_batch(values), mesh.shard_batch(keys.astype(np.int32)),
    num_segments=4,
))
np.testing.assert_array_equal(sums, np.full((4, 2), 2.0 * P))
print("keyed_aggregate:", sums[:, 0])

# --- mapPartition: run a function once per shard --------------------------
data = np.arange(P * 8, dtype=np.float64)


def per_partition(shard):
    # Each device sees its local rows; emit a per-row normalized value.
    return shard - shard.mean()


centered = np.asarray(map_partition(mesh, per_partition, mesh.shard_batch(data)))
assert centered.shape == data.shape
print("map_partition: per-shard mean removed")

# --- Host barrier: all hosts rendezvous (multi-host control plane) --------
participants = host_barrier(mesh, tag=1)
print("host_barrier participants:", participants)
