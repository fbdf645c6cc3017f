"""flinkml_tpu.cluster: the multi-process worker runtime.

Three layers of coverage:

1. transport framing edge cases against scripted sockets — torn frames,
   oversized refusal on BOTH sides, deadline expiry mid-read, worker
   death mid-response — every failure a TYPED error (the router's
   failover table is built on types, not messages);
2. the worker server + client in-process (op dispatch, error-frame
   reconstruction, batch-sized embedding exchange, request
   correlation);
3. the full multi-process scenarios in clean child processes
   (``tests/_cluster_child.py``: bitwise parity / kill-mid-traffic /
   warm respawn / cross-process lease reclaim;
   ``tests/_elastic_rank.py``: a real world-shrink resume through the
   rank-scoped snapshot family's layout tags).
"""

import json
import os
import socket
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax

from flinkml_tpu import faults
from flinkml_tpu.cluster import (
    ElasticProcessWorld,
    WorkerClient,
    rendezvous_env,
)
from flinkml_tpu.cluster import protocol
from flinkml_tpu.cluster.errors import (
    ConnectionClosedError,
    FrameError,
    OversizedFrameError,
    RemoteError,
    TransportTimeoutError,
    WorkerDiedError,
    decode_error,
    encode_error,
)
from flinkml_tpu.cluster.worker import WorkerServer
from flinkml_tpu.parallel import init_distributed
from flinkml_tpu.serving.errors import (
    ServingOverloadError,
    ServingSchemaError,
)

_HERE = os.path.dirname(os.path.abspath(__file__))


def _child_env():
    return {**os.environ, "PYTHONPATH": os.pathsep.join(
        [os.path.dirname(_HERE)]
        + ([os.environ["PYTHONPATH"]]
           if os.environ.get("PYTHONPATH") else [])
    )}


# ---------------------------------------------------------------------------
# 1. Framing edge cases (scripted sockets, no backend)
# ---------------------------------------------------------------------------

def _pair():
    a, b = socket.socketpair()
    return a, b


def test_frame_roundtrip():
    a, b = _pair()
    try:
        protocol.send_frame(a, protocol.REQUEST, 7,
                            {"op": "ping", "x": np.arange(3)})
        ftype, rid, payload = protocol.recv_frame(
            b, deadline=time.monotonic() + 2.0
        )
        assert (ftype, rid) == (protocol.REQUEST, 7)
        assert payload["op"] == "ping"
        np.testing.assert_array_equal(payload["x"], np.arange(3))
    finally:
        a.close(), b.close()


def test_torn_frame_is_typed():
    """Peer dies mid-frame: the receiver sees a FrameError naming the
    tear, never a hang or a bare EOFError."""
    a, b = _pair()
    frame = protocol.encode_frame(protocol.RESPONSE, 1, {"k": "v" * 100})
    a.sendall(frame[: len(frame) // 2])
    a.close()
    with pytest.raises(FrameError, match="torn frame"):
        protocol.recv_frame(b, deadline=time.monotonic() + 2.0)
    b.close()


def test_clean_eof_is_connection_closed():
    """EOF at a frame BOUNDARY is the distinct clean-hangup type (a
    reader loop exits quietly instead of reporting a tear)."""
    a, b = _pair()
    a.close()
    with pytest.raises(ConnectionClosedError):
        protocol.recv_frame(b, deadline=time.monotonic() + 2.0)
    b.close()


def test_bad_magic_is_typed():
    a, b = _pair()
    a.sendall(b"HTTP" + b"\x00" * (protocol.HEADER_SIZE - 4) + b"junk")
    with pytest.raises(FrameError, match="magic"):
        protocol.recv_frame(b, deadline=time.monotonic() + 2.0)
    a.close(), b.close()


def test_oversized_payload_refused_on_send():
    """The sender refuses before a byte leaves — the embedding-exchange
    guard (batch-sized payloads only)."""
    a, b = _pair()
    with pytest.raises(OversizedFrameError, match="batch-sized"):
        protocol.send_frame(a, protocol.REQUEST, 1,
                            {"rows": np.zeros(4096)}, max_payload=64)
    a.close(), b.close()


def test_oversized_header_refused_before_payload_read():
    """A peer DECLARING an oversized payload is refused at the header —
    the receiver never allocates or reads the lie."""
    a, b = _pair()
    header = struct.pack(">4sBQQ", protocol.MAGIC, protocol.RESPONSE,
                         1, 1 << 40)
    a.sendall(header)
    with pytest.raises(OversizedFrameError, match="refusing"):
        protocol.recv_frame(b, deadline=time.monotonic() + 2.0,
                            max_payload=1024)
    a.close(), b.close()


def test_deadline_expires_mid_read():
    """Half a frame then silence: the deadline is enforced PER BYTE, so
    the stall surfaces as TransportTimeoutError (a TimeoutError) at the
    deadline — not an unbounded block."""
    a, b = _pair()
    frame = protocol.encode_frame(protocol.RESPONSE, 1, {"k": "v" * 64})
    a.sendall(frame[:protocol.HEADER_SIZE + 4])  # header + partial body
    t0 = time.monotonic()
    with pytest.raises(TransportTimeoutError, match="mid-read"):
        protocol.recv_frame(b, deadline=t0 + 0.5)
    assert time.monotonic() - t0 < 5.0
    assert isinstance(TransportTimeoutError("x"), TimeoutError)
    a.close(), b.close()


def test_frame_reader_reassembles_across_polls():
    """FrameReader buffers partial bytes across poll() wakeups — a
    deadline-sweeping reader loop must never tear a slow frame."""
    a, b = _pair()
    frame = protocol.encode_frame(protocol.RESPONSE, 9, {"n": 42})
    reader = protocol.FrameReader(b)
    got = []

    def drip():
        for i in range(0, len(frame), 7):
            a.sendall(frame[i:i + 7])
            time.sleep(0.01)

    t = threading.Thread(target=drip)
    t.start()
    deadline = time.monotonic() + 5.0
    while not got and time.monotonic() < deadline:
        out = reader.poll(timeout_s=0.02)
        if out is not None:
            got.append(out)
    t.join()
    assert got and got[0][1] == 9 and got[0][2] == {"n": 42}
    a.close(), b.close()


# ---------------------------------------------------------------------------
# 2. Error frames: typed reconstruction across the boundary
# ---------------------------------------------------------------------------

def test_known_errors_cross_as_themselves():
    for exc in (ServingSchemaError("bad column"),
                ServingOverloadError("queue full"),
                OversizedFrameError("too big"),
                faults.FaultInjected("scripted")):
        back = decode_error(encode_error(exc))
        assert type(back) is type(exc)
        assert str(exc) in str(back)


def test_unknown_error_becomes_remote_error():
    payload = {"etype": "SomeWorkerOnlyError", "message": "boom"}
    back = decode_error(payload)
    assert isinstance(back, RemoteError)
    assert back.etype == "SomeWorkerOnlyError"
    assert back.remote_message == "boom"


# ---------------------------------------------------------------------------
# 3. Worker server + client in-process (fake engine; no spawn)
# ---------------------------------------------------------------------------

class _FakeResponse:
    def __init__(self, columns):
        self.columns = columns
        self.version = 3
        self.shed = False


class _FakeActive:
    def __init__(self, model):
        self.model = model


class _FakeEmbeddingStage:
    def __init__(self, vocab=64, dim=4):
        self._rows = np.arange(vocab * dim, dtype=np.float32
                               ).reshape(vocab, dim)


class _FakeEngine:
    """Just enough engine surface for WorkerServer's op table."""

    def __init__(self):
        self._active = _FakeActive(_FakeEmbeddingStage())
        self.stopped = False

    def predict(self, columns, timeout_ms=None):
        feats = np.asarray(columns["features"])
        if feats.ndim != 2:
            raise ServingSchemaError("features must be rank 2")
        return _FakeResponse({"prediction": feats.sum(axis=1)})

    def stats(self):
        return {"name": "fake"}

    def stop(self, drain=True, timeout=None):
        self.stopped = True


@pytest.fixture()
def worker_pair():
    server = WorkerServer(_FakeEngine(), name="fake", max_payload=1 << 20)
    port = server.bind()
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    client = WorkerClient("127.0.0.1", port).connect()
    yield server, client
    client.close()
    server.shutdown()


def test_worker_ops_roundtrip(worker_pair):
    _, client = worker_pair
    assert client.call("ping")["ok"] is True
    out = client.call("predict", {
        "columns": {"features": np.ones((4, 3))}, "timeout_ms": 1000,
    })
    np.testing.assert_array_equal(out["columns"]["prediction"],
                                  np.full(4, 3.0))
    assert out["version"] == 3


def test_worker_typed_error_surfaces_as_itself(worker_pair):
    """A ServingSchemaError raised inside the worker re-raises
    client-side AS ServingSchemaError — the router failover table needs
    no cluster-specific rows."""
    _, client = worker_pair
    with pytest.raises(ServingSchemaError, match="rank 2"):
        client.call("predict", {
            "columns": {"features": np.ones(3)}, "timeout_ms": 1000,
        })


def test_embedding_exchange_is_batch_sized_only(worker_pair):
    _, client = worker_pair
    out = client.call("embedding_rows", {"ids": np.array([0, 5, 2])})
    stage = _FakeEmbeddingStage()
    np.testing.assert_array_equal(out["rows"], stage._rows[[0, 5, 2]])
    # A vocab-sized request is refused with the framing cap's own typed
    # error — never a vocab-sized transfer.
    with pytest.raises(OversizedFrameError, match="batch-sized"):
        client.call("embedding_rows", {"ids": np.arange(64)})
    with pytest.raises(ValueError, match="out of range"):
        client.call("embedding_rows", {"ids": np.array([-1])})


def test_unknown_op_is_typed(worker_pair):
    _, client = worker_pair
    with pytest.raises(ValueError, match="unknown worker op"):
        client.call("nonsense")


def test_client_correlates_out_of_order_responses():
    """Two in-flight requests answered in REVERSE order each complete
    their own callback (request-id correlation, one connection)."""
    listener = socket.socket()
    listener.bind(("127.0.0.1", 0))
    listener.listen(1)
    port = listener.getsockname()[1]

    def serve():
        conn, _ = listener.accept()
        frames = [protocol.recv_frame(conn, deadline=time.monotonic() + 5)
                  for _ in range(2)]
        for ftype, rid, payload in reversed(frames):
            protocol.send_frame(conn, protocol.RESPONSE, rid,
                                {"echo": payload["tag"]})
        time.sleep(0.2)
        conn.close()

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    client = WorkerClient("127.0.0.1", port).connect()
    results = {}
    done = threading.Event()

    def on_done(tag):
        def _cb(result, error):
            results[tag] = (result, error)
            if len(results) == 2:
                done.set()
        return _cb

    client.submit("a", {"tag": "first"}, on_done=on_done("first"))
    client.submit("b", {"tag": "second"}, on_done=on_done("second"))
    assert done.wait(5.0)
    assert results["first"][0]["echo"] == "first"
    assert results["second"][0]["echo"] == "second"
    client.close()
    listener.close()


def test_worker_death_mid_response_fails_inflight_typed():
    """The worker dies after HALF a response frame: the in-flight
    request fails with WorkerDiedError (retire-and-failover signal),
    not a hang and not a parse crash."""
    listener = socket.socket()
    listener.bind(("127.0.0.1", 0))
    listener.listen(1)
    port = listener.getsockname()[1]

    def serve():
        conn, _ = listener.accept()
        protocol.recv_frame(conn, deadline=time.monotonic() + 5)
        frame = protocol.encode_frame(
            protocol.RESPONSE, 1, {"big": "x" * 4096}
        )
        conn.sendall(frame[: len(frame) // 2])  # tear it
        conn.close()

    threading.Thread(target=serve, daemon=True).start()
    client = WorkerClient("127.0.0.1", port).connect()
    box = {}
    done = threading.Event()

    def _cb(result, error):
        box["error"] = error
        done.set()

    client.submit("predict", {"x": 1}, on_done=_cb)
    assert done.wait(5.0)
    assert isinstance(box["error"], WorkerDiedError)
    client.close()
    listener.close()


def test_silent_worker_times_out_only_overdue_requests():
    """A worker that accepts and never answers: the reader sweep fails
    exactly the requests whose transport deadline passed."""
    listener = socket.socket()
    listener.bind(("127.0.0.1", 0))
    listener.listen(1)
    port = listener.getsockname()[1]
    conns = []
    threading.Thread(
        target=lambda: conns.append(listener.accept()[0]), daemon=True
    ).start()
    client = WorkerClient("127.0.0.1", port).connect()
    outcomes = {}
    events = {k: threading.Event() for k in ("soon", "later")}

    def _cb(key):
        def cb(result, error):
            outcomes[key] = error
            events[key].set()
        return cb

    now = time.monotonic()
    client.submit("a", {}, deadline=now + 0.3, on_done=_cb("soon"))
    client.submit("b", {}, deadline=now + 30.0, on_done=_cb("later"))
    assert events["soon"].wait(5.0)
    assert isinstance(outcomes["soon"], TransportTimeoutError)
    assert not events["later"].is_set()  # the healthy deadline survives
    assert client.inflight == 1
    client.close()
    listener.close()


# ---------------------------------------------------------------------------
# 4. init_distributed env family (satellite: one rendezvous path)
# ---------------------------------------------------------------------------

def _patch_rendezvous(monkeypatch):
    calls = []

    def fake_initialize(**kwargs):
        calls.append(kwargs)

    monkeypatch.setattr(jax.distributed, "initialize", fake_initialize)
    monkeypatch.setattr(jax.distributed, "is_initialized", lambda: False)
    return calls


def test_init_distributed_framework_env_wins(monkeypatch):
    """FLINKML_TPU_COORD_ADDR family beats the generic JAX_* launcher
    vars — spawned workers and operator-launched processes share ONE
    rendezvous path."""
    calls = _patch_rendezvous(monkeypatch)
    monkeypatch.setenv("FLINKML_TPU_COORD_ADDR", "10.0.0.9:9999")
    monkeypatch.setenv("FLINKML_TPU_WORLD_SIZE", "4")
    monkeypatch.setenv("FLINKML_TPU_RANK", "2")
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.1.1.1:1111")
    monkeypatch.setenv("JAX_NUM_PROCESSES", "8")
    monkeypatch.setenv("JAX_PROCESS_ID", "7")
    init_distributed()
    assert calls == [{
        "coordinator_address": "10.0.0.9:9999",
        "num_processes": 4, "process_id": 2,
    }]


def test_init_distributed_jax_env_fallback(monkeypatch):
    calls = _patch_rendezvous(monkeypatch)
    for var in ("FLINKML_TPU_COORD_ADDR", "FLINKML_TPU_WORLD_SIZE",
                "FLINKML_TPU_RANK"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.1.1.1:1111")
    monkeypatch.setenv("JAX_NUM_PROCESSES", "3")
    monkeypatch.setenv("JAX_PROCESS_ID", "1")
    init_distributed()
    assert calls == [{
        "coordinator_address": "10.1.1.1:1111",
        "num_processes": 3, "process_id": 1,
    }]


def test_init_distributed_explicit_args_beat_env(monkeypatch):
    calls = _patch_rendezvous(monkeypatch)
    monkeypatch.setenv("FLINKML_TPU_COORD_ADDR", "10.0.0.9:9999")
    monkeypatch.setenv("FLINKML_TPU_WORLD_SIZE", "4")
    monkeypatch.setenv("FLINKML_TPU_RANK", "2")
    init_distributed("10.2.2.2:2222", 2, 0)
    assert calls == [{
        "coordinator_address": "10.2.2.2:2222",
        "num_processes": 2, "process_id": 0,
    }]


def test_process_runtime_refuses_a_non_cpu_parent(monkeypatch):
    """The launchers start virtual-CPU-device children; under a parent
    whose backend is a TPU they would quietly serve from CPU workers, so
    ClusterPool and ElasticProcessWorld refuse with a typed error that
    points at the in-process ReplicaPool."""
    from flinkml_tpu.cluster import (
        ClusterPool,
        ElasticProcessWorld,
        ProcessRuntimeBackendError,
    )
    from flinkml_tpu.table import Table

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ProcessRuntimeBackendError, match="ReplicaPool"):
        ClusterPool(object(), Table({"features": np.zeros((2, 2))}))
    with pytest.raises(ProcessRuntimeBackendError, match="'tpu'"):
        ElasticProcessWorld(lambda rank, world, rnd: ["true"])


def test_rendezvous_env_exports_the_family():
    env = rendezvous_env(rank=3, world=4, port=8476, base={})
    assert env == {
        "FLINKML_TPU_COORD_ADDR": "127.0.0.1:8476",
        "FLINKML_TPU_WORLD_SIZE": "4",
        "FLINKML_TPU_RANK": "3",
    }


# ---------------------------------------------------------------------------
# 5. WorkerCrash fault (the cluster.worker seam)
# ---------------------------------------------------------------------------

def test_worker_crash_plan_json_roundtrip(tmp_path):
    marker = str(tmp_path / "crash.marker")
    plan = faults.FaultPlan(faults.WorkerCrash(
        at=5, key="epoch", exit_code=29, marker=marker,
    ))
    back = faults.plan_from_json(faults.plan_to_json(plan))
    (f,) = back.faults
    assert isinstance(f, faults.WorkerCrash)
    assert (f.at, f.key, f.exit_code, f.marker) == (5, "epoch", 29, marker)


def test_worker_crash_marker_gives_crash_once_across_restarts(tmp_path):
    """The marker file is the cross-RESTART once-flag: a restarted
    child re-arming the same plan must not die at the same trigger
    forever (``should_fire`` only — ``apply`` is a real os._exit)."""
    marker = str(tmp_path / "crash.marker")
    f = faults.WorkerCrash(at=3, key="epoch", marker=marker)
    assert not f.should_fire({"epoch": 2})
    assert f.should_fire({"epoch": 3})
    open(marker, "w").close()  # "the previous incarnation fired"
    assert not f.should_fire({"epoch": 3})


def test_fuzz_plan_requires_marker_dir_for_worker_seam(tmp_path):
    with pytest.raises(ValueError, match="marker_dir"):
        faults.FuzzPlan(seed=1, seams=("cluster.worker",))
    plan = faults.FuzzPlan(seed=1, seams=("cluster.worker",),
                           marker_dir=str(tmp_path))
    sampled = plan.sample(0)
    assert any(isinstance(f, faults.WorkerCrash) for f in sampled.faults)


# ---------------------------------------------------------------------------
# 6. The full multi-process scenarios (clean children)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster_child_report():
    """Parity / kill-mid-traffic / warm-respawn / lease-reclaim in a
    fresh interpreter (the suite conftest's jax persistent cache poisons
    XLA:CPU executable serialization in-process — the compile-count half
    of the acceptance needs a clean process; see
    ``tests/_cluster_child.py``)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(_HERE, "_cluster_child.py")],
        capture_output=True, text=True, timeout=420, env=_child_env(),
    )
    assert proc.returncode == 0, (
        f"cluster child failed:\n{proc.stdout}\n{proc.stderr}"
    )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_cluster_pool_bitwise_parity(cluster_child_report):
    rep = cluster_child_report
    assert rep["parity_bitwise"] is True, rep
    assert rep["sha_ref"] == rep["sha_pool"]


def test_worker_killed_mid_traffic_loses_zero_requests(
        cluster_child_report):
    """The acceptance pin: a WorkerCrash (real os._exit, armed over the
    transport) mid-closed-loop-traffic loses ZERO requests — the typed
    WorkerDiedError rides the router's retire-and-failover path."""
    rep = cluster_child_report
    assert rep["crashed_rc"] == 23, rep
    assert rep["requests_ok"] > 0, rep
    assert rep["requests_lost"] == 0, rep
    assert rep["health_after_crash"]["r1"] == "HEALTHY", rep


def test_respawn_rejoins_warm_zero_new_compiles(cluster_child_report):
    """A respawned worker warms from the pool's shared artifact store:
    retarget LOADS, zero new XLA compiles, parity still bitwise."""
    rep = cluster_child_report
    assert rep["respawned"], rep
    assert rep["respawn_fusion"]["compiles"] == 0.0, rep
    assert rep["respawn_fusion"]["aot_loads"] > 0, rep
    assert rep["post_respawn_parity"] is True, rep


def test_cross_process_lease_reclaim(cluster_child_report):
    """A slice lease held INSIDE a worker revokes and releases over the
    transport — the revoke→release handshake is process-transparent."""
    rep = cluster_child_report
    assert rep["lease_reclaimed"], rep
    assert all(ls["released"] for ls in rep["lease_reclaimed"]), rep


def test_cluster_metrics_published(cluster_child_report):
    rep = cluster_child_report
    assert rep["workers_alive_gauge"] == 2.0, rep
    assert rep["transport_p99_ms"] is not None, rep
    assert rep["spawn_ms_samples"] == 3, rep  # 2 initial + 1 respawn


# slow (PR 21): a process-spawning case of 20-30 s; tier-1's 870 s limit is
# tight with a cold compile cache. tools/ci.sh's full suite still runs it.
@pytest.mark.slow
def test_elastic_world_shrinks_and_resumes_bit_exact(tmp_path):
    """World size = PROCESS count: a 2-process world loses its highest
    rank to a WorkerCrash, the supervisor relaunches the survivor as
    world 1, and the survivor reassembles the rank-scoped snapshot
    family through its layout tags — finishing bit-identical to a
    continuous golden run, resumed from the crash-time epoch (never a
    silent fresh start)."""
    wd = str(tmp_path)
    script = os.path.join(_HERE, "_elastic_rank.py")
    world = ElasticProcessWorld(
        lambda rank, w, rnd: [sys.executable, script, wd],
        env=_child_env(), workdir=wd, round_timeout_s=180,
    )
    final_world = world.run(2, min_world=1)
    assert final_world == 1
    assert world.rounds[0]["lost"] == 1
    assert 23 in world.rounds[0]["exit_codes"]

    subprocess.run([sys.executable, script, wd, "golden"],
                   check=True, timeout=180, env=_child_env())
    res = json.load(open(os.path.join(wd, "result.json")))
    gold = json.load(open(os.path.join(wd, "result-golden.json")))
    assert res["resumed_from"] > 0, res  # not a silent fresh start
    assert res["w"] == gold["w"]
    assert res["rows"] == gold["rows"]
