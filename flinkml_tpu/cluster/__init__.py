"""Multi-process worker runtime: "N replicas" means N processes.

The reference system's runtime is multi-process task managers
exchanging data over Netty; the reproduction's serving/runtime layers
were single-process SPMD until this subsystem. The pieces:

- :mod:`~flinkml_tpu.cluster.protocol` / :mod:`~flinkml_tpu.cluster
  .client` — the length-prefixed local transport (request ids,
  per-byte deadlines, typed error frames);
- :mod:`~flinkml_tpu.cluster.worker` — the child harness (one
  ServingEngine behind the transport, warm via the shared compile
  cache, ``cluster.worker`` fault seam);
- :mod:`~flinkml_tpu.cluster.process` — spawn/supervise children;
- :mod:`~flinkml_tpu.cluster.remote` — the engine adapter the serving
  router dispatches over, unchanged;
- :mod:`~flinkml_tpu.cluster.pool` — :class:`ClusterPool`, a
  ReplicaPool of worker processes, plus cross-process lease reclaim
  and batch-sized embedding row exchange;
- :mod:`~flinkml_tpu.cluster.elastic` — elastic process worlds (world
  size = process count; crash → resume at the smaller world).

See ``docs/development/cluster.md``.
"""

from flinkml_tpu.cluster.client import WorkerClient
from flinkml_tpu.cluster.elastic import (
    COORD_ADDR_VAR,
    RANK_VAR,
    WORLD_SIZE_VAR,
    ElasticProcessWorld,
    free_port,
    rendezvous_env,
)
from flinkml_tpu.cluster.errors import (
    ClusterError,
    ConnectionClosedError,
    FrameError,
    OversizedFrameError,
    ProcessRuntimeBackendError,
    RemoteError,
    TransportError,
    TransportTimeoutError,
    WorkerDiedError,
    WorkerSpawnError,
)
from flinkml_tpu.cluster.pool import (
    ClusterPool,
    fetch_embedding_rows,
    reclaim_worker_leases,
)
from flinkml_tpu.cluster.process import WorkerProcess, WorkerSpec
from flinkml_tpu.cluster.remote import RemoteEngine

__all__ = [
    "COORD_ADDR_VAR",
    "RANK_VAR",
    "WORLD_SIZE_VAR",
    "ClusterError",
    "ClusterPool",
    "ConnectionClosedError",
    "ElasticProcessWorld",
    "FrameError",
    "OversizedFrameError",
    "ProcessRuntimeBackendError",
    "RemoteEngine",
    "RemoteError",
    "TransportError",
    "TransportTimeoutError",
    "WorkerClient",
    "WorkerDiedError",
    "WorkerProcess",
    "WorkerSpawnError",
    "WorkerSpec",
    "fetch_embedding_rows",
    "free_port",
    "reclaim_worker_leases",
    "rendezvous_env",
]
