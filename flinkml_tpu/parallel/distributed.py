"""Multi-host runtime: initialization, control-plane barrier, data slicing.

SURVEY.md §5 "Distributed communication backend" maps the reference's three
channels onto TPU pods:

  1. data-plane (Flink's Netty credit-based shuffles between subtasks,
     ``AllReduceImpl.java:79-93``) → XLA collectives over **ICI**, emitted
     by the compiler from shardings (see ``parallel/collectives.py``);
  2. feedback-plane (in-JVM ``FeedbackChannel`` between co-located
     tail/head, ``TailOperator.java:81-88``) → the host loop carry —
     no channel exists;
  3. control-plane (``OperatorEventGateway`` RPC between head subtasks and
     the JobManager-resident ``SharedProgressAligner``,
     ``SharedProgressAligner.java:127-158``) → **this module**: the
     ``jax.distributed`` coordination service over DCN for process startup,
     plus a device-mediated global barrier for the few host-side sync
     points (checkpoint commit, termination agreement).

On a single host everything degrades to no-ops, so the same training
script runs unchanged from a laptop CPU mesh to a multi-host pod slice.
"""

from __future__ import annotations

import os
import time
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from flinkml_tpu.utils import logging as flog

_log = flog.get_logger("distributed")

# Substrings that mark a rendezvous failure as TRANSIENT (worth retrying:
# the coordinator is still coming up, DNS lag, a dropped TCP handshake).
# Anything else — bad address, auth, rank mismatch — fails fast.
_TRANSIENT_MARKERS = (
    "unavailable",
    "deadline",
    "timed out",
    "timeout",
    "connection refused",
    "connection reset",
    "failed to connect",
    "connect failed",
    "temporarily",
    "barrier",
)


def _is_transient_rendezvous_error(err: BaseException) -> bool:
    msg = str(err).lower()
    return any(marker in msg for marker in _TRANSIENT_MARKERS)


def retry_backoff_s(attempt: int, backoff_s: float,
                    jitter: float = 0.25,
                    rng: Optional["random.Random"] = None) -> float:
    """The jittered exponential delay before retry ``attempt`` (1-based):
    ``backoff_s * 2**(attempt-1) * (1 + U[0, jitter])``.

    The jitter is the point: N ranks that hit the same transient
    rendezvous failure retry in LOCKSTEP under pure exponential backoff
    — they re-collide at the coordinator on every attempt, indefinitely.
    A per-process uniform draw decorrelates the herd (each process seeds
    from its own entropy), which is the standard
    thundering-herd-breaking shape. Exposed for tests and for other
    retry sites (the recovery engine's policy uses the same shape)."""
    import random

    if backoff_s <= 0:
        return 0.0
    base = backoff_s * (2 ** (max(int(attempt), 1) - 1))
    r = (rng or random).random()
    return base * (1.0 + max(0.0, float(jitter)) * r)


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    max_attempts: int = 3,
    backoff_s: float = 1.0,
    backoff_jitter: float = 0.25,
    deadline_s: Optional[float] = None,
) -> Tuple[int, int]:
    """Join the jax.distributed coordination service (DCN control plane).

    Call once per process before any device computation, on every host of
    the pod slice. Arguments default from the environment — first the
    framework's own rendezvous family (``FLINKML_TPU_COORD_ADDR`` /
    ``FLINKML_TPU_WORLD_SIZE`` / ``FLINKML_TPU_RANK``, what
    :mod:`flinkml_tpu.cluster`'s spawned workers and operator-launched
    processes both export, so every launcher shares ONE rendezvous
    path), then the standard ``JAX_COORDINATOR_ADDRESS`` /
    ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID`` set by most TPU
    launchers; with no coordinator configured this is a single-process
    no-op.

    Transient rendezvous failures (coordinator still booting, dropped
    connections, deadline overruns — the normal churn of a pod slice
    coming up host by host) are retried up to ``max_attempts`` times
    with exponential backoff **plus per-process jitter**
    (:func:`retry_backoff_s` — N ranks retrying in pure-exponential
    lockstep re-collide at the coordinator indefinitely; the jitter
    decorrelates them). ``deadline_s`` caps the TOTAL time spent
    rendezvousing (attempts + sleeps): when the next backoff would
    overrun it, the retry ladder stops and the last failure is raised —
    a pod that cannot form within its startup budget should fail loudly,
    not spin. Non-transient errors (bad address, rank mismatch) still
    fail fast on the first occurrence.

    Returns ``(process_index, process_count)``.
    """
    coordinator_address = (
        coordinator_address
        or os.environ.get("FLINKML_TPU_COORD_ADDR")
        or os.environ.get("JAX_COORDINATOR_ADDRESS")
    )
    num_processes = num_processes if num_processes is not None else int(
        os.environ.get("FLINKML_TPU_WORLD_SIZE")
        or os.environ.get("JAX_NUM_PROCESSES", "1")
    )
    process_id = process_id if process_id is not None else int(
        os.environ.get("FLINKML_TPU_RANK")
        or os.environ.get("JAX_PROCESS_ID", "0")
    )
    # The guard must not touch any backend-initializing API
    # (jax.process_count() et al. would create the XLA backend, after which
    # jax.distributed.initialize() unconditionally raises) — so the decision
    # is made from the arguments/environment plus jax.distributed's own
    # state, which is safe to query before backend init.
    if (
        coordinator_address
        and num_processes > 1
        and not jax.distributed.is_initialized()
    ):
        if max_attempts < 1:
            raise ValueError(f"max_attempts must be >= 1, got {max_attempts}")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        t0 = time.monotonic()
        for attempt in range(1, max_attempts + 1):
            try:
                jax.distributed.initialize(
                    coordinator_address=coordinator_address,
                    num_processes=num_processes,
                    process_id=process_id,
                )
                _log.info(
                    "rendezvous with %s succeeded (attempt %d/%d, "
                    "process %d of %d)", coordinator_address, attempt,
                    max_attempts, process_id, num_processes,
                )
                break
            except Exception as e:  # noqa: BLE001 — classified below
                delay = retry_backoff_s(attempt, backoff_s, backoff_jitter)
                elapsed = time.monotonic() - t0
                overrun = (
                    deadline_s is not None
                    and elapsed + delay > deadline_s
                )
                if (
                    attempt == max_attempts
                    or overrun
                    or not _is_transient_rendezvous_error(e)
                ):
                    _log.error(
                        "rendezvous with %s failed %s (attempt %d/%d, "
                        "%.1fs elapsed): %r",
                        coordinator_address,
                        "permanently" if attempt == max_attempts
                        else ("at the total deadline "
                              f"({deadline_s}s)" if overrun
                              else "fast (non-transient)"),
                        attempt, max_attempts, elapsed, e,
                    )
                    raise
                _log.warning(
                    "transient rendezvous failure with %s (attempt %d/%d), "
                    "retrying in %.2fs (jittered): %r", coordinator_address,
                    attempt, max_attempts, delay, e,
                )
                time.sleep(delay)
    index, count = jax.process_index(), jax.process_count()
    flog.set_rank(index, count)  # pin the log tag to the real rank
    return index, count


def host_barrier(mesh=None, tag: int = 0) -> int:
    """Global barrier across all hosts/devices; returns ``tag``'s psum.

    The SPMD data-plane is implicitly synchronized; this is for the rare
    *host-side* rendezvous (the reference used coordinator RPC +
    ``SharedProgressAligner``): e.g. "all hosts finished writing their
    checkpoint shard" before committing a manifest. Implemented as a tiny
    ``psum`` so it rides the same ICI/DCN fabric as the data plane and
    needs no extra service.

    ``mesh``: a :class:`flinkml_tpu.parallel.DeviceMesh` (defaults to a
    fresh all-devices mesh).
    """
    from flinkml_tpu.parallel.mesh import DeviceMesh

    dm = mesh if mesh is not None else DeviceMesh()
    axis = dm.axis_names[0]

    def _one(x):
        return jax.lax.psum(x, axis)

    # Build the input per-device via callback so each process only touches
    # its addressable devices — a host-local global array would need a
    # device_put onto non-addressable devices on a multi-host pod.
    global_shape = (dm.axis_size(),)
    sharding = jax.sharding.NamedSharding(dm.mesh, P(axis))
    full = np.full(global_shape, tag, dtype=np.int32)
    arr = jax.make_array_from_callback(global_shape, sharding,
                                       lambda idx: full[idx])
    summed = jax.jit(
        jax.shard_map(
            _one, mesh=dm.mesh, in_specs=P(axis), out_specs=P(None)
        )
    )(arr)
    # Host blocks until every participant contributed (output is fully
    # replicated, so every host can read shard 0 locally).
    return int(np.asarray(summed.addressable_shards[0].data)[0])


def agree_resume_epoch(manager, mesh=None, old_world: Optional[int] = None,
                       new_world: Optional[int] = None) -> Optional[int]:
    """The elastic survivors' rendezvous: agree the newest snapshot of
    ``manager`` (a :class:`~flinkml_tpu.iteration.CheckpointManager`)
    that EVERY remaining rank can restore.

    Each rank nominates its local newest verified epoch
    (``manager.newest_valid_epoch()`` — integrity-checked, so a rank
    whose shared-FS view of the latest snapshot is torn nominates the
    one before it); the agreement is then two existing rendezvous
    primitives over the same ICI/DCN fabric as the data plane:

    1. :func:`~flinkml_tpu.iteration.stream_sync.agree_all_ok` — any
       rank with NO valid snapshot at all aborts every rank together
       (resuming the others from epoch k while one starts fresh would
       split-brain the fleet);
    2. :func:`~flinkml_tpu.iteration.stream_sync.agree_min` over the
       nominated epochs — the newest COMMONLY-valid snapshot.

    Fires the ``rendezvous.rescale`` fault seam (with both worlds in
    context) so tests can script a shrink rendezvous that fails.
    Single-process this degrades to the local newest-valid epoch (None
    when the directory holds no valid snapshot — a fresh start).
    """
    import flinkml_tpu.faults as faults

    local = manager.newest_valid_epoch()
    if faults.ACTIVE is not None:  # scripted shrink-rendezvous failure
        faults.fire("rendezvous.rescale",
                    local_epoch=-1 if local is None else int(local),
                    old_world=old_world, new_world=new_world)
    if jax.process_count() == 1:
        _log.info(
            "elastic resume rendezvous (single process): newest valid "
            "epoch %s under %s", local, manager.directory,
        )
        return local
    from flinkml_tpu.iteration.stream_sync import agree_all_ok, agree_min

    agree_all_ok(
        local is not None, mesh,
        f"elastic resume: a valid snapshot under {manager.directory}",
    )
    agreed = agree_min(int(local), mesh)
    # min-of-newest is only COMMONLY valid if every survivor still holds
    # (and can verify) that epoch — a rank whose older snapshots were
    # pruned (max_to_keep) or torn in its shared-FS view would otherwise
    # discover the gap mid-restore and strand the peers in the training
    # collectives: exactly the split-brain the rendezvous exists to
    # prevent. Abort together instead.
    agree_all_ok(
        agreed == local or manager.verify(agreed), mesh,
        f"elastic resume: agreed snapshot epoch {agreed} restorable on "
        "every survivor",
    )
    _log.info(
        "elastic resume rendezvous: local newest valid epoch %s, agreed "
        "epoch %s (world %s -> %s)", local, agreed, old_world, new_world,
    )
    return agreed


def compact_rank(old_rank: int, lost_ranks) -> Optional[int]:
    """A survivor's process id in the shrunken world: its position among
    the surviving old ranks (dense, order-preserving — old rank 3 with
    rank 1 lost becomes new rank 2). None when ``old_rank`` is itself
    lost. This is the id a survivor passes to :func:`rescale_world`."""
    lost = set(int(r) for r in lost_ranks)
    old_rank = int(old_rank)
    if old_rank in lost:
        return None
    return old_rank - sum(1 for r in lost if r < old_rank)


def rescale_world(new_world: int, new_rank: int,
                  coordinator_address: Optional[str] = None,
                  **init_kwargs) -> Tuple[int, int]:
    """Re-join the coordination service at a NEW world size — the
    control-plane half of an elastic shrink/grow: tear down the old
    ``jax.distributed`` membership (if any) and rendezvous again as
    process ``new_rank`` of ``new_world`` (survivor ranks compacted via
    :func:`compact_rank`). Single-host (no coordinator configured, world
    1) this is a no-op returning ``(0, 1)`` — the CPU test path.

    The data-plane re-layout is NOT here: restore the carry through a
    ``rescale="reshard"`` manager and re-split the feed via its cursor
    (see ``docs/development/fault_tolerance.md``, "Elastic resume").
    """
    new_world, new_rank = int(new_world), int(new_rank)
    if new_world < 1 or not (0 <= new_rank < new_world):
        raise ValueError(
            f"invalid rescaled assignment rank {new_rank} of {new_world}"
        )
    if jax.distributed.is_initialized():
        _log.warning("leaving old world for rescale (rank %d of new %d)",
                     new_rank, new_world)
        jax.distributed.shutdown()
    if new_world == 1 and not (
        coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS")
    ):
        flog.set_rank(0, 1)
        return 0, 1
    return init_distributed(
        coordinator_address=coordinator_address,
        num_processes=new_world,
        process_id=new_rank,
        **init_kwargs,
    )


def require_single_controller(what: str) -> None:
    """Raise a clear error when ``what`` runs under a multi-process mesh.

    Most streamed out-of-core fits ARE multi-process-capable (round 4:
    the linear family, KMeans, GMM, GBT, PCA, and the streamed-Adam
    runner behind MLP/FM train from per-process stream partitions via
    ``iteration/stream_sync.py``). The families still guarded here keep
    id-keyed or per-document host state in layouts that are not yet
    process-partitioned (ALS's factor blocks, LDA's document
    statistics, Word2Vec's pair cache) — on a multi-process mesh they would
    die opaquely inside ``device_put`` (non-addressable devices), so the
    defined behavior is this explicit rejection; multi-host training for
    them uses the in-RAM paths with ``mesh.global_batch`` per-host
    ingest (``examples/multihost_pod.py``).
    """
    if jax.process_count() > 1:
        _log.error("%s rejected under a multi-process mesh "
                   "(single-controller only)", what)
        raise RuntimeError(
            f"{what} is single-controller: it places full global batches "
            "from one process, which cannot address a multi-process "
            "mesh's remote devices. Run it single-process, or use the "
            "in-RAM fit with per-host `mesh.global_batch` ingest "
            "(docs/development/parallelism.md, examples/multihost_pod.py). "
            "Multi-process streamed fits are available for the linear "
            "family, KMeans, GaussianMixture, GBT, PCA, and MLP/FM."
        )


def process_slice(n: int, process_index: Optional[int] = None,
                  process_count: Optional[int] = None) -> slice:
    """This host's contiguous row range of a global dataset of ``n`` rows.

    Multi-host input pipeline convention: each host reads only its slice
    (the reference's per-subtask stream partitions), then shards it over
    its addressable devices; global batch = concat of host slices.
    Remainder rows go to the low-index hosts, one each.
    """
    p = jax.process_index() if process_index is None else process_index
    c = jax.process_count() if process_count is None else process_count
    base, rem = divmod(n, c)
    start = p * base + min(p, rem)
    return slice(start, start + base + (1 if p < rem else 0))
