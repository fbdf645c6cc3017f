"""Pass 2 (collective-order checker) tests.

Covers: jaxpr collective extraction (plain, jitted, shard_map, loop
bodies), cross-rank order divergence (FML301), the PR 1 threaded-kmeans
deadlock fixture (FML302 on the unlocked shape, silence on the locked
shape), per-mesh tracked locks, and the live integration: a real
threaded ``train_kmeans_stream`` run records a dispatch trace the
checker certifies safe — the lock is analyzer-verified, not assumed.
"""

import threading

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from flinkml_tpu.analysis import (
    CollectiveOp,
    DispatchEvent,
    check_dispatch_trace,
    check_rank_order,
    extract_collectives,
    load_trace,
)
from flinkml_tpu.parallel import dispatch
from flinkml_tpu.parallel.dispatch import (
    TrackedRLock,
    held_lock_tokens,
    local_execution_lock,
)

DEADLOCK_TRACE = "tests/analysis_fixtures/kmeans_threaded_deadlock.trace.json"
LOCKED_TRACE = "tests/analysis_fixtures/kmeans_threaded_locked.trace.json"
POOL_TRACE = "tests/analysis_fixtures/pool_slice_unlocked.trace.json"


# ---------------------------------------------------------------------------
# jaxpr extraction
# ---------------------------------------------------------------------------

def test_extract_collectives_order_and_axes():
    def f(x):
        s = jax.lax.psum(x, "data")
        m = jax.lax.pmax(s, "data")
        return jax.lax.pmin(m, "data")

    # axis_env form: trace with a bound axis.
    closed = jax.make_jaxpr(f, axis_env=[("data", 4)])(jnp.ones(3))
    from flinkml_tpu.analysis.collectives import _walk_jaxpr
    out = []
    _walk_jaxpr(closed.jaxpr, out)
    assert [c.primitive for c in out] == ["psum", "pmax", "pmin"]
    assert all(c.axes == ("data",) for c in out)


def test_extract_collectives_through_jit_shard_map_and_loops(mesh):
    """The real framework shape: a jitted shard_map program with
    collectives inside a fori_loop body — extraction recurses into every
    sub-jaxpr and reports the loop body's sequence once."""
    from flinkml_tpu.models.kmeans import _kmeans_partial_fn
    from flinkml_tpu.parallel.mesh import DeviceMesh

    fn = _kmeans_partial_fn(mesh.mesh, 3, DeviceMesh.DATA_AXIS)
    x = jnp.ones((16, 4))
    w = jnp.ones(16)
    c = jnp.ones((3, 4))
    seq = extract_collectives(fn, x, w, c)
    assert [op.primitive for op in seq] == ["psum", "psum"]
    assert all(op.axes == (DeviceMesh.DATA_AXIS,) for op in seq)


def test_rank_order_divergence_fml301():
    a = (CollectiveOp("psum", ("data",)), CollectiveOp("all_gather", ("data",)))
    b = (CollectiveOp("all_gather", ("data",)), CollectiveOp("psum", ("data",)))
    assert not check_rank_order({0: a, 1: a})
    findings = check_rank_order({0: a, 1: b}, program="step")
    assert len(findings) == 1 and findings[0].rule == "FML301"
    assert "rank 1" in findings[0].message


# ---------------------------------------------------------------------------
# the PR 1 deadlock fixture
# ---------------------------------------------------------------------------

def test_deadlock_fixture_flagged_and_locked_fixture_passes():
    """Satellite acceptance: the checker flags the unlocked threaded-
    kmeans program shape (two threads, shared 8-device mesh, no common
    lock) and passes the identical schedule under the per-mesh lock."""
    unlocked = load_trace(DEADLOCK_TRACE)
    findings = check_dispatch_trace(unlocked, location=DEADLOCK_TRACE)
    assert [f.rule for f in findings] == ["FML302"]
    assert "kmeans.lloyd_epoch" in findings[0].message

    locked = load_trace(LOCKED_TRACE)
    assert check_dispatch_trace(locked, location=LOCKED_TRACE) == []


def test_dispatch_trace_rules():
    def ev(thread, devices, locks=()):
        return DispatchEvent(thread=thread, program="p", devices=devices,
                             locks=tuple(locks))

    # Single-device programs never rendezvous across devices: no finding.
    assert not check_dispatch_trace([ev("a", (0,)), ev("b", (0,))])
    # Disjoint device sets: no finding.
    assert not check_dispatch_trace([ev("a", (0, 1)), ev("b", (2, 3))])
    # Same thread: ordered by program order: no finding.
    assert not check_dispatch_trace([ev("a", (0, 1)), ev("a", (0, 1))])
    # Overlapping multi-device, different threads, no common lock: flagged.
    assert check_dispatch_trace([ev("a", (0, 1)), ev("b", (1, 2))])
    # A shared lock token clears it; different locks do not.
    assert not check_dispatch_trace(
        [ev("a", (0, 1), ["L"]), ev("b", (1, 2), ["L"])]
    )
    assert check_dispatch_trace(
        [ev("a", (0, 1), ["L1"]), ev("b", (1, 2), ["L2"])]
    )


def test_pool_slice_overlap_fml303():
    """The FML302 pair machinery specializes to FML303 when one side is
    a serving replica-pool slice dispatch (program prefix
    ``serving.pool/``): the unlocked shape is flagged with the
    pool-specific rule and fix hint, a shared slice lock clears it, and
    the seeded bad-trace fixture is flagged through the file loader."""
    def ev(thread, program, devices, locks=()):
        return DispatchEvent(thread=thread, program=program,
                             devices=devices, locks=tuple(locks))

    pool_ev = ev("serving-p0/r0", "serving.pool/p0/r0.batch", (0, 1))
    train = ev("trainer", "kmeans.lloyd_epoch", (0, 1, 2, 3),
               ["lock:mesh:0,1,2,3"])
    findings = check_dispatch_trace([pool_ev, train])
    assert [f.rule for f in findings] == ["FML303"]
    assert "serving.pool/p0/r0.batch" in findings[0].message
    assert "slice" in findings[0].fix_hint

    # The replica holding its slice lock composes with the overlapping
    # training lock (overlap => the trainer's composite includes it).
    locked_pool = ev("serving-p0/r0", "serving.pool/p0/r0.batch", (0, 1),
                     ["lock:mesh:0,1"])
    locked_train = ev("trainer", "kmeans.lloyd_epoch", (0, 1, 2, 3),
                      ["lock:mesh:0,1,2,3", "lock:mesh:0,1"])
    assert check_dispatch_trace([locked_pool, locked_train]) == []

    # Single-device replicas dispatch no collectives: never flagged.
    assert check_dispatch_trace(
        [ev("serving-p0/r0", "serving.pool/p0/r0.batch", (0,)), train]
    ) == []

    # Two pool replicas over overlapping slices without a shared lock is
    # the same hazard (a misconfigured pool): also FML303.
    other = ev("serving-p0/r1", "serving.pool/p0/r1.batch", (1, 2))
    assert [f.rule for f in check_dispatch_trace([pool_ev, other])] == [
        "FML303"
    ]

    fixture = load_trace(POOL_TRACE)
    flagged = check_dispatch_trace(fixture, location=POOL_TRACE)
    assert [f.rule for f in flagged] == ["FML303"]


def test_local_execution_lock_accepts_device_sequences():
    """Per-slice lock composition without a mesh object: a plain device
    (id) sequence keys the same tracked lock as an identical mesh set,
    so pool replicas and trainers compose through one registry."""
    locks_before = set(dispatch._MESH_LOCKS)
    try:
        lock_a = local_execution_lock([901, 902])
        lock_b = local_execution_lock((902, 901))
        with lock_a:
            tokens = held_lock_tokens()
            assert "lock:mesh:901,902" in tokens
        # Identical set -> the same TrackedRLock instance.
        assert lock_a is lock_b or getattr(lock_a, "token", None) == getattr(
            lock_b, "token", None
        )
        # Overlapping sets compose: acquiring the overlap holds both
        # tokens.
        composite = local_execution_lock([902, 903])
        with composite:
            tokens = held_lock_tokens()
            assert "lock:mesh:901,902" in tokens
            assert "lock:mesh:902,903" in tokens
    finally:
        # The fake id sets must not linger in the process-wide registry
        # (a global-lock holder would acquire them forever after).
        with dispatch._MESH_LOCKS_GUARD:
            for key in set(dispatch._MESH_LOCKS) - locks_before:
                del dispatch._MESH_LOCKS[key]


# ---------------------------------------------------------------------------
# tracked locks + live recording
# ---------------------------------------------------------------------------

def test_tracked_lock_tokens_and_reentrancy():
    lock = TrackedRLock("lock:test")
    assert "lock:test" not in held_lock_tokens()
    with lock:
        assert "lock:test" in held_lock_tokens()
        with lock:  # reentrant
            assert "lock:test" in held_lock_tokens()
        assert "lock:test" in held_lock_tokens()
    assert "lock:test" not in held_lock_tokens()


def test_per_mesh_lock_registry(mesh, monkeypatch):
    # An empty registry: a device set that overlaps this mesh's, left by
    # an earlier test of the same worker process, would make every call a
    # new composite (which files share a worker changes with the suite).
    monkeypatch.setattr(dispatch, "_MESH_LOCKS", {})
    # Same device set -> same lock object.
    assert local_execution_lock(mesh) is local_execution_lock(mesh)
    mesh_token = local_execution_lock(mesh).token
    assert mesh_token.startswith("lock:mesh:")
    # mesh=None is globally exclusive: it acquires the process lock AND
    # every registered mesh lock, so it shares a token with any
    # concurrent mesh-keyed fit (the FML302-safe shape).
    with local_execution_lock():
        tokens = set(held_lock_tokens())
    assert "lock:process" in tokens
    assert mesh_token in tokens


def test_overlapping_mesh_locks_share_a_component():
    """Overlapping-but-unequal device sets must still exclude each other:
    the later request gets a composite acquiring every intersecting lock
    (in canonical order), so any two overlapping fits share a token — the
    shape the FML302 check certifies."""

    class FakeDev:
        def __init__(self, i):
            self.id = i

    class FakeMesh:
        def __init__(self, ids):
            self.devices = np.array([FakeDev(i) for i in ids], dtype=object)

    a = local_execution_lock(FakeMesh([100, 101]))
    b = local_execution_lock(FakeMesh([101, 102]))  # overlaps a
    c = local_execution_lock(FakeMesh([200, 201]))  # disjoint from both

    with a:
        tokens_a = set(held_lock_tokens())
    with b:
        tokens_b = set(held_lock_tokens())
    with c:
        tokens_c = set(held_lock_tokens())
    assert tokens_a & tokens_b, "overlapping sets must share a lock token"
    assert not (tokens_c & (tokens_a | tokens_b)), "disjoint sets must not"

    # And the shared component actually excludes: b cannot be acquired
    # while a is held.
    entered = []
    with a:
        t = threading.Thread(target=lambda: (b.acquire(), entered.append(1),
                                             b.release()))
        t.start()
        t.join(timeout=0.3)
        assert not entered, "composite must block while the base lock is held"
    t.join(timeout=5)
    assert entered


def test_process_lock_excludes_mesh_locks():
    """mesh=None must serialize against mesh-keyed fits: its composite
    holds every registered mesh lock, so a mesh fit cannot start while a
    process-wide loop runs (and vice versa)."""

    class FakeDev:
        def __init__(self, i):
            self.id = i

    class FakeMesh:
        def __init__(self, ids):
            self.devices = np.array([FakeDev(i) for i in ids], dtype=object)

    mesh_lock = local_execution_lock(FakeMesh([300, 301]))
    entered = []
    with local_execution_lock():  # globally exclusive
        assert mesh_lock.token in held_lock_tokens()
        t = threading.Thread(
            target=lambda: (mesh_lock.acquire(), entered.append(1),
                            mesh_lock.release())
        )
        t.start()
        t.join(timeout=0.3)
        assert not entered, "mesh fit must wait for the process-wide holder"
    t.join(timeout=5)
    assert entered


def test_record_collective_dispatch_unlocked_vs_locked(mesh):
    """The synthetic reproduction of the PR 1 shape through the REAL
    recording machinery: two threads record epoch dispatches over the
    mesh — without the lock the checker flags FML302, with it the trace
    is clean."""
    device_ids = tuple(d.id for d in mesh.mesh.devices.flatten())

    def run(locked):
        events = []
        dispatch.add_dispatch_observer(events.append)
        try:
            def fit(name):
                if locked:
                    with local_execution_lock(mesh):
                        dispatch.record_collective_dispatch(
                            "kmeans.lloyd_epoch", device_ids
                        )
                else:
                    dispatch.record_collective_dispatch(
                        "kmeans.lloyd_epoch", device_ids
                    )

            threads = [
                threading.Thread(target=fit, args=(f"fit-{i}",))
                for i in range(2)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            dispatch.remove_dispatch_observer(events.append)
        return [DispatchEvent.from_map(e) for e in events]

    unlocked = run(locked=False)
    assert [f.rule for f in check_dispatch_trace(unlocked)] == ["FML302"]
    locked = run(locked=True)
    assert check_dispatch_trace(locked) == []


def test_threaded_train_kmeans_stream_trace_is_analyzer_safe(mesh):
    """Integration: two genuinely concurrent train_kmeans_stream fits
    record a dispatch trace that the collective-order checker certifies
    deadlock-free — the per-mesh lock PR 1 introduced is now verified by
    the analyzer instead of trusted."""
    from flinkml_tpu.models.kmeans import train_kmeans_stream

    rng = np.random.default_rng(5)
    x = rng.normal(size=(64, 3)).astype(np.float32)
    init = np.ascontiguousarray(x[:2])

    def batches():
        for off in range(0, 64, 32):
            yield {"x": x[off:off + 32]}

    events = []
    dispatch.add_dispatch_observer(events.append)
    try:
        threads = [
            threading.Thread(
                target=train_kmeans_stream,
                args=(iter(list(batches())),),
                kwargs=dict(k=2, mesh=mesh, max_iter=2, seed=0,
                            initial_centroids=init),
            )
            for _ in range(2)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        dispatch.remove_dispatch_observer(events.append)

    trace = [DispatchEvent.from_map(e) for e in events]
    # Both fits recorded their epochs (2 threads x 2 epochs)...
    assert len(trace) == 4
    assert all(e.locks for e in trace), "epochs must dispatch under a lock"
    # ...and the recorded shape is the safe one.
    assert check_dispatch_trace(trace) == []


# ---------------------------------------------------------------------------
# FML304 — slice leases (training/serving colocation, ISSUE 15)
# ---------------------------------------------------------------------------

LEASE_TRACE = "tests/analysis_fixtures/pool_lease_unreclaimed.trace.json"


def test_pool_lease_unreclaimed_fixture_fml304():
    """The seeded fixture: a pool dispatch on a still-leased slice is
    FML304 even though it HOLDS the shared slice lock — leases are a
    capacity contract, orthogonal to rendezvous locking."""
    events = load_trace(LEASE_TRACE)
    findings = check_dispatch_trace(events, location=LEASE_TRACE)
    assert [f.rule for f in findings] == ["FML304"]
    assert "lease:trainer:0,1" in findings[0].message
    assert "request_revoke" in (findings[0].fix_hint or "")


def test_fml304_live_lease_recording_and_release():
    """Live shape: dispatch events record active FOREIGN leases over
    their devices; the holder's own dispatches do not carry the token;
    releasing the lease clears later events (the reclaim handshake's
    observable end state)."""
    lease = dispatch.lease_devices([0, 1], holder="trainer304")
    events = []
    dispatch.add_dispatch_observer(events.append)
    try:
        # Holder thread: its own dispatch carries no foreign lease.
        dispatch.record_collective_dispatch("train_step", [0, 1])

        def pool_dispatch():
            dispatch.record_collective_dispatch(
                "serving.pool/p304/r0.batch", [1, 2]
            )

        t = threading.Thread(target=pool_dispatch)
        t.start()
        t.join()
        lease.release()
        t2 = threading.Thread(target=pool_dispatch)
        t2.start()
        t2.join()
    finally:
        dispatch.remove_dispatch_observer(events.append)
        lease.release()
    assert events[0]["leases"] == ()
    assert events[1]["leases"] == (lease.token,)
    assert events[2]["leases"] == ()  # released: reclaimed slice is clean
    trace = [DispatchEvent.from_map(e) for e in events]
    rules = [f.rule for f in check_dispatch_trace(trace)]
    assert rules.count("FML304") == 1


def test_fml304_non_pool_dispatch_on_lease_not_flagged():
    """A second TRAINER overlapping a lease is a scheduling question,
    not the serving-steals-leased-slice shape — FML304 is pool-only
    (FML302 still covers the locking side)."""
    events = [
        DispatchEvent(thread="t1", program="train_a", devices=(0, 1),
                      locks=("lock:mesh:0,1",),
                      leases=("lease:other:0,1",)),
    ]
    assert [f.rule for f in check_dispatch_trace(events)] == []


def test_lease_registry_duplicate_refused():
    lease = dispatch.lease_devices([4, 5], holder="dup")
    try:
        with pytest.raises(ValueError, match="already registered"):
            dispatch.lease_devices([4, 5], holder="dup")
    finally:
        lease.release()
    # Released: the same slice can be leased again.
    again = dispatch.lease_devices([4, 5], holder="dup")
    again.release()
