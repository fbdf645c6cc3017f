"""Persistent AOT compile cache: store round-trips, invalidation rules,
corruption robustness, concurrency, and the replica-pool one-compile
contract (ISSUE 11).

The serialization-dependent scenarios run ONCE in a clean child process
(``tests/_compile_cache_child.py``) and are asserted over here: once
jax's persistent compilation cache — which the suite's conftest enables —
LOADS one executable in a process, XLA:CPU registers its jit-kernels as
resident-but-not-re-emittable and every later compile sharing a
content-identical kernel serializes broken (the store's post-serialize
load check refuses such artifacts by design; `test_poisoned_serialize_
degrades_in_this_process` pins exactly that). A fresh process is also
the production cold-start shape the subsystem exists for. The remaining
tests (key semantics, activation, degraded modes) run in-process.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from flinkml_tpu import compile_cache, pipeline_fusion
from flinkml_tpu.compile_cache.store import CompileCacheStore, _key_hash
from flinkml_tpu.table import Table
from flinkml_tpu.utils.metrics import metrics


@pytest.fixture(autouse=True)
def _clean_cache_state():
    """Every test starts with no active store and an empty program
    cache, and leaves the process the same way (other test modules
    count compiles)."""
    compile_cache.reset()
    compile_cache.configure(None)
    pipeline_fusion.reset_cache()
    yield
    compile_cache.reset()
    compile_cache.configure(None)
    pipeline_fusion.reset_cache()


@pytest.fixture(scope="module")
def child_report():
    """The clean-process scenario report (one child run per module)."""
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "_compile_cache_child.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ,
             "PYTHONPATH": os.pathsep.join(
                 [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
                 + ([os.environ["PYTHONPATH"]]
                    if os.environ.get("PYTHONPATH") else [])
             )},
    )
    assert proc.returncode == 0, (
        f"compile-cache child scenarios crashed rc={proc.returncode}:\n"
        f"{proc.stderr[-3000:]}"
    )
    return json.loads(proc.stdout.strip().splitlines()[-1])


# -- clean-process scenarios (see module docstring) --------------------------


def test_disk_roundtrip_bitwise_parity(child_report):
    """Cold run compiles + stores; a fresh store over the same directory
    loads from disk; outputs are bitwise identical to the plain jit
    path both ways."""
    r = child_report["roundtrip"]
    assert r["stores"] > 0
    assert r["aot_files"] == r["stores"]
    assert r["warm_hits"] == r["stores"]  # every program loaded, none...
    assert r["warm_extra_misses"] == 0    # ...recompiled
    assert r["cold_bitwise"] and r["warm_bitwise"]


def test_corrupt_entry_falls_back_loudly(child_report):
    """Torn/corrupt cache entries log a warning, are deleted, and the
    caller recompiles and REWRITES them — correctness is never at the
    cache's mercy."""
    r = child_report["corrupt"]
    assert r["corrupt_entries"] == r["torn_files"] > 0
    assert r["warned"], "corruption was silent"
    assert r["served_bitwise"]
    assert r["rewritten_hits"] > 0  # replaced artifacts load again


def test_env_fingerprint_mismatch_invalidates(child_report):
    """A jax-version bump changes the env-hash namespace, and even a
    byte-identical artifact copied across namespaces is refused by the
    embedded env dict — never loaded stale."""
    r = child_report["env_mismatch"]
    assert r["namespaces_differ"]
    assert r["copied_entry_refused"]
    assert r["env_mismatches"] == 1


def test_concurrent_writers_share_one_build(child_report):
    """Racing get_or_compile calls on one key pay ONE build (per-key
    lock); independent stores racing on one path never publish a torn
    entry (temp-file + os.replace), and the entry reloads from disk."""
    r = child_report["race"]
    assert r["results"] == r["racing_threads"] == 4
    assert r["builds_one_store"] == 1
    assert r["compiled_outcomes"] == 1
    assert r["reload_outcome"] == "disk"
    assert r["reload_correct"]


def test_pool_spinup_pays_one_compile_per_program(child_report):
    """The ISSUE 11 bugfix pin: an N-replica pool warms the same
    (program, bucket, policy) identities ONCE — replica 0 compiles,
    every other replica loads the retargeted artifact. Without the
    shared store each per-device placement silently re-paid the full
    XLA compile inside jax.jit."""
    r = child_report["pool"]
    assert r["programs"] > 0
    assert r["misses"] == r["programs"]          # one compile per program
    assert r["hits"] == 3 * r["programs"]        # 3 replicas load it
    assert r["retarget_loads"] >= 2 * r["programs"]
    assert r["steady_state_compiles"] == 0
    assert r["bitwise_vs_direct"]


def test_retargeted_load_cross_device_parity(child_report):
    """One artifact compiled on the default device serves a transform
    pinned to a different device bitwise-identically."""
    r = child_report["retarget"]
    assert r["retarget_loads"] > 0
    assert r["bitwise"]


def test_plan_step_disk_roundtrip(child_report):
    """The third compile site: a fresh process's plan-sharded trainer
    loads its step executable from disk, numerically identical."""
    r = child_report["plan_step"]
    assert r["cold_misses"] >= 1 and r["cold_stores"] >= 1
    assert r["warm_hits"] >= 1
    assert r["cold_equal"] and r["warm_equal"]


# -- in-process behavior -----------------------------------------------------


def _fitted_mini_chain():
    from flinkml_tpu.models.scalers import MaxAbsScaler, StandardScaler
    from flinkml_tpu.pipeline import PipelineModel

    rng = np.random.default_rng(1)
    x = rng.normal(size=(70, 7))
    t = Table({"features": x})
    scaler = (StandardScaler().set(StandardScaler.INPUT_COL, "features")
              .set(StandardScaler.OUTPUT_COL, "mid").fit(t))
    (t1,) = scaler.transform(t)
    mx = (MaxAbsScaler().set(MaxAbsScaler.INPUT_COL, "mid")
          .set(MaxAbsScaler.OUTPUT_COL, "scaled").fit(t1))
    # TWO kernel stages, because only runs of >= 2 route through the
    # fused executor (the compile-cache seam).
    return PipelineModel([scaler, mx]), t


def test_poisoned_serialize_degrades_in_this_process():
    """In THIS process — where the suite's jax persistent compilation
    cache has loaded executables — an unserializable program must
    degrade to compile-only (post-serialize load check or serialize
    failure), never crash and never persist a bad artifact. Whichever
    way this process's history falls, transforms keep serving and every
    on-disk artifact is loadable."""
    import tempfile

    scaler, t = _fitted_mini_chain()
    (baseline,) = scaler.transform(t)
    base = np.asarray(baseline.column("scaled"))
    d = tempfile.mkdtemp(prefix="cc-inproc-")
    compile_cache.configure(d)
    pipeline_fusion.reset_cache()
    (out,) = scaler.transform(t)
    assert np.asarray(out.column("scaled")).tobytes() == base.tobytes()
    # Whatever was persisted must load in a fresh store; a poisoned
    # program must NOT have been persisted at all.
    stored = [os.path.join(r, f) for r, _, fs in os.walk(d)
              for f in fs if f.endswith(".aot")]
    compile_cache.reset()
    compile_cache.configure(d)
    pipeline_fusion.reset_cache()
    before = metrics.group("compile_cache").snapshot()["counters"]
    (again,) = scaler.transform(t)
    after = metrics.group("compile_cache").snapshot()["counters"]
    assert np.asarray(again.column("scaled")).tobytes() == base.tobytes()
    assert after.get("corrupt_entries", 0) == before.get(
        "corrupt_entries", 0
    ), "a poisoned artifact reached disk"
    if stored:
        assert after.get("hits", 0) > before.get("hits", 0)


def test_memory_store_shares_within_process():
    """A directory-less store dedupes compiles in-process (what
    ReplicaPool relies on) and persists nothing."""
    store = CompileCacheStore(None)
    compile_cache.configure(store)
    scaler, t = _fitted_mini_chain()
    scaler.transform(t)
    misses1 = metrics.group("compile_cache").snapshot()["counters"].get(
        "misses", 0
    )
    assert misses1 > 0
    pipeline_fusion.reset_cache()
    # reset_cache drops the store's memory layer too — re-transform
    # recompiles (no disk behind a memory store).
    scaler.transform(Table({"features": np.asarray(t.column("features"))}))
    misses2 = metrics.group("compile_cache").snapshot()["counters"].get(
        "misses", 0
    )
    assert misses2 > misses1
    assert store.entry_path(("k",)) is None


def test_stable_key_repr_and_hash():
    from flinkml_tpu.precision import resolve_policy
    from flinkml_tpu.sharding.plan import FSDP, FSDP_TP

    policy = resolve_policy("mixed")
    k1 = ("pipeline_fusion", ("fp", 8, policy), FSDP)
    k2 = ("pipeline_fusion", ("fp", 8, resolve_policy("mixed")), FSDP)
    assert compile_cache.stable_key_repr(k1) == \
        compile_cache.stable_key_repr(k2)
    assert _key_hash(k1) == _key_hash(k2)
    assert _key_hash(k1) != _key_hash(
        ("pipeline_fusion", ("fp", 8, policy), FSDP_TP)
    )
    # dicts render order-independently
    assert compile_cache.stable_key_repr({"b": 1, "a": 2}) == \
        compile_cache.stable_key_repr(dict([("a", 2), ("b", 1)]))


def test_env_var_activates_store(tmp_path, monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_DIR_VAR, str(tmp_path))
    compile_cache.reset()
    store = compile_cache.active_store()
    assert store is not None and store.directory == str(tmp_path)
    compile_cache.reset()
    monkeypatch.delenv(compile_cache.ENV_DIR_VAR)
    assert compile_cache.active_store() is None
