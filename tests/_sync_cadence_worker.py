"""Multi-process worker: sustained cross-process collective dispatch.

Regression for the multi-process in-flight-dispatch deadlock: a bare host
loop enqueueing 60 ``psum`` steps with no synchronization wedges a
2-process Gloo mesh permanently (threshold between 20 and 60 in-flight).
``synced_loop`` is the framework's backpressure policy (the role Flink's
credit-based flow control plays under ``AllReduceImpl.java:52-299``);
this worker (launched as an N-process pod) drives 80 sustained steps through it — more than the wedge
trigger — and checks the numeric result.

Usage: python _sync_cadence_worker.py <port> <process_id> <num_processes>
Prints ``CADENCE_OK <pid>`` on success.
"""

import os
import sys

port, pid, nproc = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])

os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from flinkml_tpu.parallel import (  # noqa: E402
    DeviceMesh,
    default_sync_interval,
    init_distributed,
    synced_loop,
)

init_distributed(
    coordinator_address=f"127.0.0.1:{port}",
    num_processes=nproc,
    process_id=pid,
)
assert default_sync_interval() > 0, (
    "multi-process mesh must default to a bounded dispatch interval"
)

dm = DeviceMesh()
axis = DeviceMesh.DATA_AXIS

def body(acc, contrib):
    return acc + jax.lax.psum(jnp.sum(contrib, 0), axis)

stepper = jax.jit(jax.shard_map(
    body, mesh=dm.mesh, in_specs=(P(), P(axis)), out_specs=P(),
))

n_dev = dm.num_devices
contrib_local = np.ones((jax.local_device_count(), 2), dtype=np.float32)
contrib = jax.make_array_from_process_local_data(
    dm.data_sharding(), contrib_local
)

N_STEPS = 80  # > the 60-step trigger that wedges an unsynchronized loop
acc = synced_loop(N_STEPS, lambda c, i: stepper(c, contrib),
                  jnp.zeros(2, jnp.float32))
got = np.asarray(acc.addressable_shards[0].data)
assert np.allclose(got, N_STEPS * n_dev), (got, N_STEPS * n_dev)

print(f"CADENCE_OK {pid}", flush=True)
