#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives fit, transform, serving, the streamed (checkpointed) fit, native
ingest, a Pallas kernel and — on more than one chip — plan-sharded
training once each, through the entry points a user calls, in ONE
process, and checks every phase's result by the repo's own means. It is
a bring-up check, not a benchmark: the ``wall_s`` it prints are not
measurements of anything but this script.

    python chip_smoke.py              # needs a TPU; exits non-zero without one
    python chip_smoke.py --rehearse   # tiny sizes, any backend, Pallas interpreted

Contract (what the driver checks): refuses to run unless
``jax.default_backend() == "tpu"``; prints the device and the compile
cache directory first, then one JSON line per phase; a phase that fails
raises, so the run exits non-zero and the result line is never printed;
the LAST stdout line of a passing run is
``{"ok": true, "device": {"platform", "kind", "count"}}``. It starts no
child process that imports JAX (a chip belongs to one process).

Sizes: dense LR at BASELINE.json config 1's width (d = 123) with 2 GB
resident; a hashed sparse LR (dim 1e6, 39 nnz/row); the benchmark's
five-stage transform chain (``benchmark/drivers/chain_model.py``) over
1M float32 rows, held to ``benchmark/reference``; a replica pool with
one replica per chip. ``--rehearse`` keeps every width and cuts
rows/steps so tier-1 can run the same code on the CPU mesh.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import tempfile
import threading
import time

import numpy as np

from benchmark import datagen
from benchmark.drivers import chain_model
from benchmark.reference import chain as reference_chain
from benchmark.reference.linear import log_loss

FULL = dict(
    dense_n=4_194_304, dense_d=123, dense_gbs=262_144, dense_steps=32,
    sparse_n=262_144, sparse_dim=1_000_000, sparse_nnz=39,
    sparse_gbs=65_536, sparse_steps=8,
    chain_n=1_048_576, chain_d=123, chain_sample=4_096,
    serve_requests=400, serve_clients=8,
    stream_batches=16, stream_rows=65_536, stream_interval=4,
    stream_crash_epoch=10,
    ingest_rows=65_536,
    topk_shape=(1_024, 8_192), topk_k=16,
    multichip_n=65_536, multichip_d=4_096, multichip_steps=8,
)
REHEARSAL = dict(
    FULL,
    dense_n=16_384, dense_gbs=4_096, dense_steps=8,
    sparse_n=2_048, sparse_dim=65_536, sparse_gbs=512,
    chain_n=8_192, chain_sample=512,
    serve_requests=40, serve_clients=4,
    stream_batches=8, stream_rows=256, stream_interval=2,
    stream_crash_epoch=5,
    ingest_rows=512,
    topk_shape=(64, 512), topk_k=8,
    multichip_n=2_048, multichip_d=256, multichip_steps=4,
)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class _DeviceWatch:
    """What a fit put on the devices, seen from outside: a thread polls
    ``jax.live_arrays()`` while the fit runs and keeps, per array shape,
    how many devices held it and whether it was sharded (not merely
    replicated). The estimators expose no handle on their device data,
    and this is the one observation that does not go through them. It
    polls every millisecond until the first array of the watched size
    shows (a rehearsal fit on a warm cache lives for a few tens of
    milliseconds), then every 50 ms."""

    def __init__(self, min_bytes: int):
        self._min_bytes = min_bytes
        self._stop = threading.Event()
        self.seen = {}  # (shape, dtype) -> (n_devices, sharded)
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _poll(self):
        import jax

        for a in jax.live_arrays():
            if a.nbytes < self._min_bytes:
                continue
            key = (tuple(a.shape), str(a.dtype))
            n = len(a.sharding.device_set)
            sharded = n > 1 and not a.sharding.is_fully_replicated
            prev = self.seen.get(key, (0, False))
            self.seen[key] = (max(prev[0], n), prev[1] or sharded)

    def _run(self):
        while not self._stop.is_set():
            self._poll()
            new = self.seen.keys() - self._before
            self._stop.wait(0.05 if new else 0.001)

    def __enter__(self):
        self._poll()  # earlier phases' leftovers: not what ends fast polling
        self._before = set(self.seen)
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=10)

    def largest(self):
        """``(shape, n_devices, sharded)`` of the biggest array seen."""
        _check(bool(self.seen), "no device array was observed during fit")
        key = max(self.seen, key=lambda k: math.prod(k[0]))
        return key[0], self.seen[key][0], self.seen[key][1]


def _assert_spread(watch: _DeviceWatch, n_devices: int, what: str) -> dict:
    shape, used, sharded = watch.largest()
    _check(used == n_devices,
           f"{what}: largest device array {shape} sat on {used} of "
           f"{n_devices} devices")
    _check(sharded or n_devices == 1,
           f"{what}: largest device array {shape} was replicated, not "
           "sharded")
    return {"largest_array": list(shape), "devices_used": used}


# -- phases ------------------------------------------------------------------


def phase_train_dense(ctx) -> dict:
    import jax

    from flinkml_tpu.models import LogisticRegression
    from flinkml_tpu.table import Table

    z = ctx.sizes
    n, d = z["dense_n"], z["dense_d"]
    rng = np.random.default_rng([ctx.seed, 1])
    x = rng.standard_normal((n, d), dtype=np.float32)
    true = rng.standard_normal(d, dtype=np.float32)
    y = (x @ true > 0).astype(np.float32)
    est = (LogisticRegression()
           .set_global_batch_size(z["dense_gbs"])
           .set_max_iter(z["dense_steps"])
           .set_learning_rate(0.5).set_tol(0.0).set_seed(ctx.seed))
    with _DeviceWatch(min_bytes=x.nbytes // (2 * len(jax.devices()))) as w:
        model = est.fit(Table({"features": x, "label": y}))
    coef = np.asarray(model.coefficient, np.float64)
    _check(coef.shape == (d,) and np.isfinite(coef).all(),
           "coefficients not finite")
    sample = slice(0, min(n, 65_536))
    margins = x[sample].astype(np.float64) @ coef
    loss = log_loss(margins, y[sample])
    acc = float(np.mean((margins >= 0) == (y[sample] > 0)))
    _check(loss < math.log(2.0), f"log-loss {loss} not below ln 2")
    _check(acc > 0.9, f"accuracy {acc} on the planted labels <= 0.9")
    spread = _assert_spread(w, len(jax.devices()), "train_dense")
    return {"rows": n, "dim": d, "steps": z["dense_steps"],
            "log_loss": loss, "accuracy": acc, **spread}


def _criteo_table(n, dim, nnz, seed, n_active=256):
    """Rows of ``nnz`` cells hashed uniformly over ``dim`` (no slot of
    theirs is blocked: the sparse trainers' GENERAL step, which no cell
    of the benchmark runs), labels planted by ``n_active`` coefficients,
    as a Table with a SparseVector column (rows that drew one column
    twice merge the two values, which is what the margins that planted
    the labels did). ``benchmark.datagen_criteo``'s rows keep to their
    fields' strata and carry logistic noise: neither fits this check."""
    from flinkml_tpu.linalg import SparseVector
    from flinkml_tpu.table import Table

    rng = np.random.default_rng(seed)
    idx = rng.integers(0, dim, size=(n, nnz)).astype(np.int64)
    val = rng.normal(size=(n, nnz)).astype(np.float32).astype(np.float64)
    beta = np.zeros(dim, np.float32)
    beta[rng.choice(dim, size=n_active, replace=False)] = rng.normal(
        size=n_active)
    y = ((val * beta[idx]).sum(axis=1) > 0).astype(np.float32)
    col = np.empty(n, object)
    for i in range(n):
        u, inv = np.unique(idx[i], return_inverse=True)
        if u.size == nnz:
            col[i] = SparseVector(dim, idx[i], val[i])
        else:
            col[i] = SparseVector(dim, u, np.bincount(inv, weights=val[i]))
    return Table({"features": col, "label": y}), idx, val, y


def phase_train_sparse(ctx) -> dict:
    import jax

    from flinkml_tpu.models import LogisticRegression

    z = ctx.sizes
    n, dim, nnz = z["sparse_n"], z["sparse_dim"], z["sparse_nnz"]
    table, idx, val, y = _criteo_table(n, dim, nnz, ctx.seed)
    est = (LogisticRegression()
           .set_global_batch_size(z["sparse_gbs"])
           .set_max_iter(z["sparse_steps"])
           .set_learning_rate(20.0).set_tol(0.0).set_seed(ctx.seed))
    with _DeviceWatch(min_bytes=n * nnz * 4 // (4 * len(jax.devices()))) as w:
        model = est.fit(table)
    coef = np.asarray(model.coefficient, np.float64)
    _check(coef.shape == (dim,) and np.isfinite(coef).all(),
           "coefficients not finite")
    margins = (val * coef[idx]).sum(axis=1)
    loss = log_loss(margins, y)
    acc = float(np.mean((margins >= 0) == (y > 0)))
    _check(loss < math.log(2.0), f"log-loss {loss} not below ln 2")
    _check(acc > 0.9, f"accuracy {acc} on the planted labels <= 0.9")
    spread = _assert_spread(w, len(jax.devices()), "train_sparse")
    return {"rows": n, "dim": dim, "nnz_per_row": nnz,
            "steps": z["sparse_steps"], "log_loss": loss, "accuracy": acc,
            **spread}


def _assert_chain_close(what, dot, ref_pred, ref_raw, pred, raw,
                        raw_tol: float) -> dict:
    """Outputs vs the float64 reference: ``rawPrediction`` within
    ``raw_tol`` absolute (they are probabilities), predictions equal
    wherever the reference margin is not within reach of 0."""
    pred = np.asarray(pred, np.float64).reshape(-1)
    raw = np.asarray(raw, np.float64)
    _check(np.isfinite(raw).all(), f"{what}: rawPrediction not finite")
    err = float(np.max(np.abs(raw - ref_raw)))
    _check(err <= raw_tol,
           f"{what}: rawPrediction off the float64 reference by {err} "
           f"(> {raw_tol})")
    # |dp/dm| <= 1/4: a margin further than 4*raw_tol from 0 cannot have
    # crossed it.
    away = np.abs(dot) > 4.0 * raw_tol
    _check(bool(np.array_equal(pred[away], ref_pred[away])),
           f"{what}: predictions differ away from the 0.5 boundary")
    return {"raw_max_abs_err": err, "rows_checked": int(raw.shape[0]),
            "rows_away_from_boundary": int(away.sum())}


def phase_transform(ctx) -> dict:
    from flinkml_tpu.table import Table
    from flinkml_tpu.utils.metrics import metrics

    z = ctx.sizes
    x = datagen.normal_matrix(
        ctx.seed, datagen.TAG_FEATURES, z["chain_n"], z["chain_d"])
    _check(x.dtype == np.float32, "chain features are not float32")
    # A coefficient that does not sum to zero leaves every margin far to
    # one side (the scaled features share an offset): probabilities
    # saturate and a comparison of them is blind. Plant a zero-sum one,
    # so margins straddle 0 and every stage's error shows.
    g = np.random.default_rng([ctx.seed, 2]).standard_normal(z["chain_d"])
    g -= g.mean()
    md = dict(datagen.chain_model_data(ctx.seed, z["chain_d"]),
              coefficient=2.0 * g / np.linalg.norm(g))
    model = chain_model.build(md)
    table = Table({"features": x})
    fusion = metrics.group("pipeline.fusion")

    def compiles() -> float:
        return fusion.snapshot()["counters"].get("compiles", 0.0)

    (out,) = model.transform(table)
    pred = np.asarray(out.column("prediction"))
    raw = np.asarray(out.column("rawPrediction"))
    after_first = compiles()
    _check(after_first >= 1, "the fused executor compiled nothing")
    (again,) = model.transform(Table({"features": x}))
    np.asarray(again.column("prediction"))
    _check(compiles() == after_first,
           "pipeline.fusion compiles rose on a second transform")
    _check(pred.shape == (z["chain_n"],) and raw.shape == (z["chain_n"], 2),
           f"unexpected output shapes {pred.shape} {raw.shape}")
    rows = np.random.default_rng([ctx.seed, 3]).choice(
        z["chain_n"], size=z["chain_sample"], replace=False)
    dot, ref_pred, ref_raw = reference_chain.chain(md, x[rows])
    close = _assert_chain_close("transform", dot, ref_pred, ref_raw,
                                pred[rows], raw[rows], ctx.raw_tol)
    ctx.chain = dict(model=model, x=x, pred=pred, raw=raw)
    devices = sorted(d.id for d in out.device_column("prediction").devices())
    return {"rows": z["chain_n"], "dim": z["chain_d"],
            "input_dtype": str(x.dtype), "output_dtype": str(raw.dtype),
            "compiles": after_first, **close,
            # Outside a pool the fused executor uploads with a bare
            # jnp.asarray: one chip, whatever the host has.
            "device_ids_used": devices}


def phase_serve(ctx) -> dict:
    import jax

    from flinkml_tpu.serving import ReplicaPool
    from flinkml_tpu.table import Table
    from flinkml_tpu.utils.metrics import metrics

    z = ctx.sizes
    model, x = ctx.chain["model"], ctx.chain["x"]
    pred, raw = ctx.chain["pred"], ctx.chain["raw"]
    n_dev = len(jax.devices())
    fusion = metrics.group("pipeline.fusion")
    cc = metrics.group("compile_cache")
    cc_before = dict(cc.snapshot()["counters"])
    pool = ReplicaPool(
        model, Table({"features": x[:4]}), n_replicas=n_dev,
        output_cols=("prediction", "rawPrediction"), name="chip_smoke",
    ).start()
    try:
        placed = [r.device.id for r in pool.replicas]
        _check(len(set(placed)) == n_dev,
               f"replicas sit on devices {placed}, not on {n_dev} distinct")
        warm = fusion.snapshot()["counters"].get("compiles", 0.0)
        per_client = z["serve_requests"] // z["serve_clients"]
        errors, worst = [], [0.0]
        lock = threading.Lock()

        def client(tid: int) -> None:
            rng = np.random.default_rng([ctx.seed, 4, tid])
            try:
                for _ in range(per_client):
                    rows = int(rng.integers(1, 33))
                    lo = int(rng.integers(0, x.shape[0] - rows))
                    resp = pool.predict({"features": x[lo:lo + rows]})
                    got_p = np.asarray(resp.columns["prediction"])
                    got_r = np.asarray(resp.columns["rawPrediction"])
                    # Same model, same rows, another batch shape: equal
                    # to phase 3's outputs up to float32 rounding.
                    err = float(np.max(np.abs(
                        got_r.astype(np.float64) - raw[lo:lo + rows])))
                    flips = got_p.reshape(-1) != pred[lo:lo + rows]
                    near = np.abs(raw[lo:lo + rows, 1] - 0.5) <= 1e-4
                    if err > 1e-5 or bool(np.any(flips & ~near)):
                        raise AssertionError(
                            f"rows {lo}:{lo + rows} differ from the "
                            f"transform output (raw err {err})")
                    with lock:
                        worst[0] = max(worst[0], err)
            except BaseException as e:  # noqa: BLE001 — reported below
                errors.append(repr(e))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(z["serve_clients"])]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        _check(not any(t.is_alive() for t in threads),
               "a serving client did not finish")
        _check(not errors, f"serving errors: {errors[:3]}")
        steady = fusion.snapshot()["counters"].get("compiles", 0.0) - warm
        _check(steady == 0, f"{steady} compiles after warm-up")
        stats = pool.stats()
        served = [int(r["counters"].get("requests", 0))
                  for r in stats["per_replica"].values()]
    finally:
        pool.stop()
    cc_after = cc.snapshot()["counters"]
    delta = {k: cc_after.get(k, 0.0) - cc_before.get(k, 0.0)
             for k in ("hits", "misses", "retarget_loads",
                       "corrupt_entries", "fallbacks")}
    _check(delta["corrupt_entries"] == 0,
           f"compile_cache corrupt_entries rose by {delta['corrupt_entries']}")
    if n_dev > 1:
        _check(delta["retarget_loads"] >= n_dev - 1,
               f"retarget_loads {delta['retarget_loads']} < replicas - 1")
    return {"replicas": n_dev, "replica_device_ids": placed,
            "requests": per_client * z["serve_clients"],
            "requests_per_replica": served,
            "compiles_after_warmup": steady,
            "max_abs_diff_vs_transform": worst[0],
            "compile_cache": delta}


def phase_stream(ctx) -> dict:
    from flinkml_tpu import faults
    from flinkml_tpu.data import Dataset
    from flinkml_tpu.iteration import CheckpointManager
    from flinkml_tpu.models import OnlineLogisticRegression
    from flinkml_tpu.table import Table

    z = ctx.sizes
    rows, d = z["stream_rows"], z["dense_d"]
    true = np.random.default_rng([ctx.seed, 5]).standard_normal(d)

    def make_batch(index, rng):
        x = rng.standard_normal((rows, d), dtype=np.float32)
        return Table({"features": x,
                      "label": (x @ true > 0).astype(np.float32)})

    def feed():
        return Dataset.synthetic(
            make_batch, z["stream_batches"], seed=ctx.seed
        ).prefetch(depth=2)

    def est():
        return OnlineLogisticRegression().set_alpha(0.5).set_reg(0.01)

    golden = est().fit_stream(feed())
    with tempfile.TemporaryDirectory(prefix="chip-smoke-ckpt-") as td:
        mgr = CheckpointManager(td, max_to_keep=10)
        crashed = False
        with faults.armed(faults.FaultPlan(
                faults.RaiseAtEpoch(z["stream_crash_epoch"]))):
            try:
                est().fit_stream(feed(), checkpoint_manager=mgr,
                                 checkpoint_interval=z["stream_interval"])
            except faults.FaultInjected:
                crashed = True
        _check(crashed, "the injected mid-stream crash did not fire")
        snapshot_epoch = mgr.latest_epoch()
        _check(snapshot_epoch is not None
               and 0 < snapshot_epoch < z["stream_batches"],
               f"no mid-stream snapshot (latest epoch {snapshot_epoch})")
        resumed = est().fit_stream(
            feed(), checkpoint_manager=mgr,
            checkpoint_interval=z["stream_interval"], resume=True)
    a = np.asarray(golden.coefficient)
    b = np.asarray(resumed.coefficient)
    _check(np.isfinite(a).all(), "streamed coefficients not finite")
    _check(a.tobytes() == b.tobytes(),
           "resumed coefficients are not bit-equal to the uninterrupted "
           f"run's (max diff {np.max(np.abs(a - b))})")
    _check(resumed.model_version == z["stream_batches"],
           f"resumed model consumed {resumed.model_version} batches")
    return {"batches": z["stream_batches"], "rows_per_batch": rows,
            "dim": d, "resumed_from_epoch": int(snapshot_epoch),
            "bit_equal": True}


def phase_ingest(ctx) -> dict:
    from flinkml_tpu.io import _native, libsvm

    z = ctx.sizes
    n, d, nnz = z["ingest_rows"], 123, 14  # a9a's profile
    rng = np.random.default_rng([ctx.seed, 6])
    cols = np.sort(rng.random((n, d)).argsort(axis=1)[:, :nnz], axis=1)
    vals = rng.standard_normal((n, nnz)).astype(np.float32)
    labels = rng.integers(0, 2, n).astype(np.float64)
    so = _native.artifact_path("libsvm_parser")
    if os.path.exists(so):
        os.remove(so)  # a library from an earlier run must not pass
    with tempfile.TemporaryDirectory(prefix="chip-smoke-svm-") as td:
        path = os.path.join(td, "data.svm")
        with open(path, "w") as fh:
            for i in range(n):
                feats = " ".join(
                    f"{c + 1}:{float(v)!r}" for c, v in zip(cols[i], vals[i]))
                fh.write(f"{int(labels[i])} {feats}\n")
        t0 = time.time()
        got_y, indptr, indices, values, nf = libsvm.read_libsvm(
            path, n_features=d)
    _check(libsvm._load_native() is not None,
           "the native libsvm parser is not loaded (Python fallback ran)")
    _check(os.path.exists(so) and os.path.getmtime(so) >= t0 - 2.0,
           f"{so} was not built in this run from flinkml_tpu/native/*.cpp")
    _check(np.array_equal(got_y, labels), "labels differ")
    _check(np.array_equal(indptr, np.arange(n + 1) * nnz), "indptr differs")
    _check(np.array_equal(indices, cols.reshape(-1)), "indices differ")
    _check(np.array_equal(values, vals.reshape(-1)), "values differ")
    return {"rows": n, "nnz": int(indptr[-1]), "n_features": int(nf),
            "native_library": os.path.basename(so), "built_this_run": True}


def phase_kernels(ctx) -> dict:
    """The top-k kernel at the shape the tiled KNN fallback ranks a tile
    with: it compiles (``interpret=False`` on the chip) and its values and
    indices are ``lax.top_k``'s."""
    import jax
    import jax.numpy as jnp

    from flinkml_tpu import kernels
    from flinkml_tpu.kernels import topk as k_topk

    z = ctx.sizes
    interpret = kernels.interpret_mode()
    if ctx.rehearse:
        _check(interpret or jax.default_backend() == "tpu",
               "rehearsal off the TPU must interpret the kernels")
    else:
        _check(not interpret,
               "kernels would run interpreted on the TPU backend "
               f"({kernels.ENV_INTERPRET_VAR} is set?)")
    rng = np.random.default_rng([ctx.seed, 7])
    xq = jnp.asarray(rng.standard_normal(z["topk_shape"], dtype=np.float32))
    reason = k_topk.unsupported_reason(xq, z["topk_k"], interpret)
    _check(reason is None, f"topk refuses the KNN fallback's tile: {reason}")
    pv, pi = jax.jit(lambda q: k_topk.pallas_top_k(
        q, z["topk_k"], interpret=interpret))(xq)
    rv, ri = jax.jit(lambda q: jax.lax.top_k(q, z["topk_k"]))(xq)
    _check(bool(np.array_equal(np.asarray(pi), np.asarray(ri))),
           "topk indices differ from lax.top_k")
    _check(bool(np.array_equal(np.asarray(pv), np.asarray(rv))),
           "topk values differ from lax.top_k")
    return {"interpret": bool(interpret),
            "topk": "interpreted" if interpret else "compiled"}


def phase_multichip(ctx) -> dict:
    import jax

    import __graft_entry__
    from flinkml_tpu.parallel import DeviceMesh
    from flinkml_tpu.sharding.apply import train_linear_plan
    from flinkml_tpu.sharding.plan import FSDP

    z = ctx.sizes
    n_dev = len(jax.devices())
    n, d = z["multichip_n"], z["multichip_d"]
    rng = np.random.default_rng([ctx.seed, 8])
    x = rng.standard_normal((n, d), dtype=np.float32)
    true = rng.standard_normal(d, dtype=np.float32)
    y = (x @ true > 0).astype(np.float32)
    mesh = DeviceMesh.for_plan(FSDP)
    with _DeviceWatch(min_bytes=d) as w:
        coef = train_linear_plan(
            x, y, None, FSDP, mesh, loss="logistic",
            max_iter=z["multichip_steps"], learning_rate=0.5)
    _check(coef.shape == (d,) and np.isfinite(coef).all(),
           "FSDP coefficients not finite")
    margins = x.astype(np.float64) @ coef.astype(np.float64)
    loss = log_loss(margins, y)
    _check(loss < math.log(2.0), f"FSDP log-loss {loss} not below ln 2")
    # Parameters and the momentum slot are both [d] float32: sharded
    # over every device, not replicated.
    state = w.seen.get(((d,), "float32"))
    _check(state is not None, "no [d] parameter array was observed")
    _check(state == (n_dev, True),
           f"parameter/optimizer state [d] sat on {state[0]} of {n_dev} "
           f"devices, sharded={state[1]}")
    batch_shape, used, sharded = w.largest()
    _check(used == n_dev and sharded,
           f"FSDP batch {batch_shape} sat on {used} devices")
    with contextlib.redirect_stdout(sys.stderr):  # it prints a summary
        __graft_entry__.dryrun_multichip(n_dev)
    return {"devices_used": n_dev, "dim": d, "plan": "fsdp",
            "log_loss": loss, "dryrun_multichip": n_dev}


PHASES = (
    ("train_dense", phase_train_dense),
    ("train_sparse", phase_train_sparse),
    ("transform", phase_transform),
    ("serve", phase_serve),
    ("stream", phase_stream),
    ("ingest", phase_ingest),
    ("kernels", phase_kernels),
    ("multichip", phase_multichip),
)


class _Context:
    def __init__(self, seed: int, rehearse: bool):
        self.seed = seed
        self.rehearse = rehearse
        self.sizes = REHEARSAL if rehearse else FULL
        self.chain = None  # phase 3's model and outputs, for phases 4 and 7
        # How far a float32 probability may sit from the float64
        # reference: float32 rounding through five stages (measured
        # 1.4e-6 on a v5e and 4.6e-7 on XLA:CPU, PR 21).
        self.raw_tol = 1e-4


def _count_cache_events() -> dict:
    """Start counting JAX's persistent-compilation-cache hits and misses
    (the listener lives as long as the process, like the script)."""
    import jax

    counts = {"hits": 0, "misses": 0}

    def listener(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(listener)
    return counts


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny sizes on whatever backend is present")
    args = parser.parse_args(argv)

    import jax

    from flinkml_tpu.utils import jax_cache

    backend = jax.default_backend()
    if backend != "tpu" and not args.rehearse:
        print(f"chip_smoke: needs a TPU, and jax.default_backend() is "
              f"{backend!r}; nothing was run (--rehearse runs tiny sizes "
              "on any backend)", file=sys.stderr)
        return 2
    cache_dir = jax_cache.enable()
    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}
    stamp = {"platform": dev0.platform}
    if args.rehearse:
        stamp["rehearsal"] = True

    def emit(record: dict) -> None:
        print(json.dumps({**record, **stamp}), flush=True)

    emit({"phase": "start", "device": device, "seed": args.seed,
          "compile_cache_dir": cache_dir,
          "compile_cache_entries": _n_entries(cache_dir),
          "jax": jax.__version__})
    ctx = _Context(args.seed, args.rehearse)
    t_start = time.perf_counter()
    cache = _count_cache_events()
    for name, phase in PHASES:
        if name == "multichip" and device["count"] < 2:
            continue
        t0 = time.perf_counter()
        try:
            result = phase(ctx)
        except BaseException as e:
            emit({"phase": name, "ok": False,
                  "error": f"{type(e).__name__}: {e}"[:2000]})
            raise
        emit({"phase": name, "ok": True,
              "wall_s": round(time.perf_counter() - t0, 2), **result})
    emit({"phase": "summary", "ok": True,
          "wall_s": round(time.perf_counter() - t_start, 2),
          "persistent_cache_hits": cache["hits"],
          "persistent_cache_misses": cache["misses"],
          "compile_cache_entries": _n_entries(cache_dir)})
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def _n_entries(cache_dir: str) -> int:
    """Executables in JAX's persistent cache directory."""
    if not os.path.isdir(cache_dir):
        return 0
    return sum(1 for f in os.listdir(cache_dir) if f.endswith("-cache"))


if __name__ == "__main__":
    sys.exit(main())
