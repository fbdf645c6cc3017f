"""Two-process jax.distributed worker, launched by test_distributed.py.

Exercises the real multi-process branch of the DCN control plane
(``init_distributed`` → ``jax.distributed.initialize``), a global mesh
spanning both processes, ``host_barrier`` across non-addressable devices,
``process_slice`` partitioning, a cross-process data-plane psum, and the
multi-host checkpoint commit ordering (every host finishes its shard →
barrier → host 0 commits the manifest → barrier → everyone sees it) —
the role SharedProgressAligner.java:127-158 plays in the reference.

Usage: python _dist_worker.py <port> <process_id> <num_processes> <workdir>
Prints ``WORKER_OK <pid>`` on success; any assertion kills the exit code.
"""

import json
import os
import sys

port, pid, nproc, workdir = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
)

os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

from flinkml_tpu.iteration.checkpoint import CheckpointManager  # noqa: E402
from flinkml_tpu.parallel import (  # noqa: E402
    DeviceMesh,
    host_barrier,
    init_distributed,
    process_slice,
)

# --- control plane startup (the branch single-process tests cannot reach).
idx, count = init_distributed(
    coordinator_address=f"127.0.0.1:{port}",
    num_processes=nproc,
    process_id=pid,
)
assert (idx, count) == (pid, nproc), (idx, count)
# Idempotent: a second call must be a no-op, not a crash.
idx2, count2 = init_distributed(
    coordinator_address=f"127.0.0.1:{port}",
    num_processes=nproc,
    process_id=pid,
)
assert (idx2, count2) == (pid, nproc)

# --- global mesh over every process's devices.
dm = DeviceMesh()
assert dm.num_devices == jax.device_count()
assert jax.device_count() == nproc * jax.local_device_count()

# --- barrier rides devices this process cannot address (the fix under test:
# the input must be materialized per-addressable-device, not host-globally).
assert host_barrier(dm, tag=1) == dm.axis_size()
assert host_barrier(dm, tag=5) == 5 * dm.axis_size()

# --- host data partitioning.
s = process_slice(10)
all_slices = [process_slice(10, p, nproc) for p in range(nproc)]
assert s == all_slices[pid]
covered = [i for sl in all_slices for i in range(sl.start, sl.stop)]
assert covered == list(range(10)), covered

# --- data plane: a psum across processes through the collectives helper.
import numpy as np  # noqa: E402
from flinkml_tpu.parallel.collectives import (  # noqa: E402
    all_reduce_sum,
    keyed_aggregate,
    map_partition,
)

n_local_dev = jax.local_device_count()
local = np.full((n_local_dev, 4), float(pid + 1), dtype=np.float32)
global_batch = jax.make_array_from_process_local_data(
    dm.data_sharding(), local
)
summed = all_reduce_sum(dm, global_batch)
expected = sum((p + 1) * n_local_dev for p in range(nproc))
got = np.asarray(summed.addressable_shards[0].data)
assert np.allclose(got, expected), (got, expected)

# --- keyed aggregation across processes (segment_sum + psum): rows on
# every device contribute to shared key buckets.
rows_per_dev = 4
vals_local = np.ones((n_local_dev * rows_per_dev, 2), dtype=np.float32)
keys_local = np.tile(
    np.arange(rows_per_dev, dtype=np.int32), n_local_dev
)
vals_g = jax.make_array_from_process_local_data(dm.data_sharding(), vals_local)
keys_g = jax.make_array_from_process_local_data(dm.data_sharding(), keys_local)
agg = keyed_aggregate(dm, vals_g, keys_g, num_segments=rows_per_dev)
agg_host = np.asarray(agg.addressable_shards[0].data)
total_devices = nproc * n_local_dev
assert np.allclose(agg_host, np.full((rows_per_dev, 2), total_devices)), agg_host

# --- mapPartition across processes: per-shard function, sharded output.
part = map_partition(
    dm, lambda shard: shard - shard.sum(), vals_g
)
# Every shard has rows_per_dev ones per column -> shard.sum() = 2*rows_per_dev.
local_out = np.concatenate(
    [np.asarray(s.data) for s in part.addressable_shards]
)
assert np.allclose(local_out, 1.0 - 2.0 * rows_per_dev), local_out[:2]

# --- checkpoint commit ordering: shard files → barrier → manifest commit
# by host 0 → barrier → visible everywhere (the two-phase commit the
# reference delegates to Flink's checkpoint coordinator).
shard_path = os.path.join(workdir, f"shard-{pid}.npz")
np.savez(shard_path, data=np.full((2,), pid, dtype=np.int64))
host_barrier(dm, tag=2)
manifest = os.path.join(workdir, "manifest.json")
if pid == 0:
    # Every shard must already exist — the barrier guaranteed it.
    shards = [f"shard-{p}.npz" for p in range(nproc)]
    missing = [f for f in shards if not os.path.exists(os.path.join(workdir, f))]
    assert not missing, missing
    mgr = CheckpointManager(
        os.path.join(workdir, "ckpt"), world_size=dm.num_devices
    )
    mgr.save({"w": np.arange(3.0)}, epoch=7, extra={"shards": shards})
    tmp = manifest + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"epoch": 7, "shards": shards}, f)
    os.replace(tmp, manifest)
host_barrier(dm, tag=3)
# After the commit barrier every process must see the manifest + checkpoint.
assert os.path.exists(manifest)
with open(manifest) as f:
    assert json.load(f)["epoch"] == 7
mgr = CheckpointManager(
    os.path.join(workdir, "ckpt"), world_size=dm.num_devices
)
state, epoch = mgr.restore_latest(like={"w": np.zeros(3)})
assert epoch == 7 and np.array_equal(state["w"], np.arange(3.0))

print(f"WORKER_OK {pid}", flush=True)
