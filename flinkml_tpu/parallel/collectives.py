"""Distributed primitives — the §2.5 checklist as XLA collectives.

Reference → TPU mapping (SURVEY.md §2.5):

  - ``DataStreamUtils.allReduceSum`` (``AllReduceImpl.java:52-299``: 3-hop
    chunked reduce-scatter + all-gather over keyed Netty shuffles, 4KB
    chunks) → :func:`all_reduce_sum`: one fused ``jax.lax.psum`` over ICI.
  - ``BroadcastUtils.withBroadcastStream`` (per-TM cache + blocking wrapper,
    ``BroadcastUtils.java:67-155``) → :func:`broadcast`: a replicated
    sharding; no caching/blocking machinery exists because SPMD replication
    is a data placement, not a runtime protocol.
  - keyed ``keyBy``+window/reduce aggregation (KMeans ``KMeans.java:174-235``,
    NaiveBayes, OneHotEncoder) → :func:`keyed_aggregate`: per-shard
    ``segment_sum`` + cross-device psum.
  - ``DataStreamUtils.mapPartition`` (buffer-all-then-apply operator,
    ``DataStreamUtils.java:62-106``) → :func:`map_partition`: a per-shard
    function under ``shard_map`` — the shard IS the partition, already
    materialized, so no ListState buffering exists.

All functions accept host numpy or device arrays and are jit-compatible when
used with device inputs (each wraps a ``jax.shard_map`` region).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from flinkml_tpu.parallel.mesh import DeviceMesh


def all_reduce_sum(mesh: DeviceMesh, contributions) -> jax.Array:
    """Sum per-device contributions; every device gets the full result.

    ``contributions``: array of shape ``[P, ...]`` (one slice per device, as
    in the reference where each of P subtasks holds one ``double[]``) or
    ``[P*k, ...]`` — the leading axis is sharded over the data axis and
    summed away.

    Replaces ``AllReduceImpl.allReduceSum``; the 4KB chunking, chunk→task
    routing and reassembly (AllReduceImpl.java:69-232) all disappear into a
    single ICI collective.
    """
    axis = DeviceMesh.DATA_AXIS

    def local_sum(x):
        return jax.lax.psum(jnp.sum(x, axis=0), axis)

    return jax.shard_map(
        local_sum, mesh=mesh.mesh, in_specs=P(axis), out_specs=P()
    )(contributions)


def broadcast(mesh: DeviceMesh, tree):
    """Replicate value(s) to all devices — the broadcast-variable analog."""
    return mesh.replicate(tree)


def keyed_aggregate(
    mesh: DeviceMesh, values, keys, num_segments: int
) -> jax.Array:
    """Sum ``values`` grouped by integer ``keys``; replicated result.

    values: ``[n, ...]`` (leading axis sharded over data), keys: ``[n]``
    int32 in ``[0, num_segments)``. Returns ``[num_segments, ...]`` summed
    across all shards — the keyed shuffle+reduce of the reference collapsed
    into on-device segment-sum + one psum.
    """
    axis = DeviceMesh.DATA_AXIS

    def local(v, k):
        seg = jax.ops.segment_sum(v, k, num_segments=num_segments)
        return jax.lax.psum(seg, axis)

    return jax.shard_map(
        local, mesh=mesh.mesh, in_specs=(P(axis), P(axis)), out_specs=P()
    )(values, jnp.asarray(keys, dtype=jnp.int32))


def map_partition(
    mesh: DeviceMesh,
    fn: Callable,
    *arrays,
    out_specs=None,
):
    """Apply ``fn`` once per shard (= per partition) of the inputs.

    ``fn`` receives each input's local shard (leading axis = local rows) and
    must return array(s) of fixed shape; with the default ``out_specs`` the
    per-shard results are concatenated along the leading axis, mirroring
    ``mapPartition``'s one-output-stream-per-partition. Pass ``out_specs=P()``
    for functions whose result is already replicated (e.g. after an
    internal psum).
    """
    axis = DeviceMesh.DATA_AXIS
    if out_specs is None:
        out_specs = P(axis)
    in_specs = tuple(P(axis) for _ in arrays)
    return jax.shard_map(
        fn, mesh=mesh.mesh, in_specs=in_specs, out_specs=out_specs
    )(*arrays)
