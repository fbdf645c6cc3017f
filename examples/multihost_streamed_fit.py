"""Multi-process streamed (out-of-core) training across a pod.

Round-4 capability: the streamed fits (linear family, KMeans,
GaussianMixture, MLP/FM) train across a multi-process mesh from
PER-PROCESS stream partitions — the reference's per-subtask stream
partitions (`ReplayOperator.java:62-250` replays each subtask's cached
partition), without any single host ever holding the global dataset.

Each host feeds only its own batches; `iteration/stream_sync.py` agrees
the SPMD schedule (fixed batch height, per-epoch step count — short
hosts dispatch zero-weight dummy steps), pools init samples across
hosts, and commits checkpoints rank-0-write + barrier. The fitted model
is replicated and bit-identical on every host.

Run on a real pod (once per host, standard launcher env vars):

    JAX_COORDINATOR_ADDRESS=<host0>:8476 \
    JAX_NUM_PROCESSES=<hosts> \
    JAX_PROCESS_ID=<this host> \
    python multihost_streamed_fit.py --worker <shared-dir>

or as a self-contained 2-process localhost demo (CPU devices):

    python multihost_streamed_fit.py --local-demo
"""

import os
import sys
import tempfile


def worker(workdir: str) -> None:
    import jax
    import numpy as np

    from flinkml_tpu.models import KMeans, LogisticRegression
    from flinkml_tpu.parallel import (
        DeviceMesh,
        init_distributed,
        process_slice,
    )
    from flinkml_tpu.table import Table

    pid, nproc = init_distributed()
    mesh = DeviceMesh()
    print(f"[proc {pid}] {jax.local_device_count()} local / "
          f"{jax.device_count()} global devices")

    # A "too big for one host" dataset: this host materializes ONLY its
    # process_slice, as a stream of batch Tables (in production: read
    # your shard of files and yield batches).
    n, d = 100_000, 16
    rng = np.random.default_rng(0)
    w_true = rng.normal(size=d).astype(np.float32)
    sl = process_slice(n)
    my_batches = []
    for start in range(sl.start, sl.stop, 8192):
        rows = min(8192, sl.stop - start)
        r = np.random.default_rng(1000 + start)  # seeded by global offset
        x = r.normal(size=(rows, d)).astype(np.float32)
        y = (x @ w_true > 0).astype(np.float32)
        my_batches.append(Table({"features": x, "label": y}))

    model = (
        LogisticRegression(mesh=mesh)
        .set_max_iter(20).set_learning_rate(0.5).set_reg(1e-4)
        .fit(iter(my_batches))
    )
    coef = np.asarray(model.get_model_data()[0].column("coefficient"))
    # Direction recovery (labels are noiseless): cosine with the truth.
    cos = float(
        coef @ w_true / (np.linalg.norm(coef) * np.linalg.norm(w_true))
    )
    print(f"[proc {pid}] LR cosine(coef, w_true) = {cos:.4f}")
    assert cos > 0.95, cos

    km = (
        KMeans(mesh=mesh).set_k(8).set_max_iter(10).set_seed(3)
        .fit(iter(
            Table({"features": t.column("features")}) for t in my_batches
        ))
    )
    cents = np.asarray(km.get_model_data()[0].column("centroids"))
    print(f"[proc {pid}] KMeans centroids {cents.shape}, "
          f"norm {np.linalg.norm(cents):.3f}")

    np.save(os.path.join(workdir, f"coef_{pid}.npy"), coef)
    print(f"[proc {pid}] done")


def _local_demo() -> None:
    """Spawn a 2-process localhost pod (Gloo over CPU) running worker()."""
    import socket
    import subprocess

    import numpy as np

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    workdir = tempfile.mkdtemp(prefix="multihost-stream-")
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "JAX_NUM_PROCESSES": "2",
            "JAX_PROCESS_ID": str(pid),
        })
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker", workdir],
            env=env,
        ))
    try:
        codes = [p.wait(timeout=600) for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    assert codes == [0, 0], codes
    a = np.load(os.path.join(workdir, "coef_0.npy"))
    b = np.load(os.path.join(workdir, "coef_1.npy"))
    assert np.array_equal(a, b)
    print("local demo OK: both hosts fitted the identical model from "
          "disjoint stream partitions")


if __name__ == "__main__":
    # Standalone-runnable (python examples/multihost_streamed_fit.py):
    # worker subprocesses get sys.path[0]=examples/, so put the repo root
    # on sys.path when flinkml_tpu isn't already importable.
    try:
        import flinkml_tpu  # noqa: F401
    except ImportError:
        sys.path.insert(
            0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    if "--worker" in sys.argv:
        worker(sys.argv[sys.argv.index("--worker") + 1])
    else:
        _local_demo()
