"""The on-disk AOT artifact store behind :mod:`flinkml_tpu.compile_cache`.

Key schema
----------

An artifact is addressed by TWO fingerprints:

1. The **program key** — whatever hashable identity the compile site
   already uses for its in-memory cache (the fused executor's ``(chain
   fingerprint, ext specs, const specs, outputs, bucket, policy)``
   tuple; the plan step's ``(mesh topology, plan, hypers, policy,
   shapes)``), rendered canonically by :func:`stable_key_repr` and
   hashed. The keys were built hashable and collision-tested for the
   in-memory caches; this module only adds persistence.
2. The **environment fingerprint** — jax/jaxlib version, backend
   platform, device kind, device count, PJRT platform version, and the
   ambient x64 flag (:func:`env_fingerprint`). A serialized executable
   is machine code for one runtime; a jax upgrade, a backend switch, or
   a different device kind MUST miss, never load a stale executable.

On disk: ``<dir>/<env_hash>/<key_hash>.aot`` (plus ``ENV.json``
describing the environment, for operators). One file per artifact; the
entry embeds its own env dict and a payload sha256, so a copied-in or
bit-rotted file is refused at read time even if it lands in the right
directory.

Invalidation rules
------------------

- env mismatch (different ``env_hash``, or an embedded env dict that
  disagrees at read time) → **miss** (counted ``env_mismatches``);
- torn/corrupt entry (unpicklable, wrong format, sha mismatch) →
  **miss**, logged loudly, the entry is deleted, and the caller's fresh
  compile rewrites it (counted ``corrupt_entries``) — never a crash;
- an executable that refuses ``serialize``, or whose artifact fails
  its post-serialize load check → that program stays in-memory only,
  logged loudly (counted ``fallbacks``).

Concurrency: entries are written to a temp file in the cache directory
and published with ``os.replace`` (the ``CheckpointManager`` idiom), so
concurrent writers — N replicas, N processes — cannot tear each other;
last writer wins with an identical artifact. In-process, a per-key lock
makes racing compilers share ONE build (the replica-pool spin-up fix:
N replicas pay one compile, N-1 artifact loads).

Device retargeting: single-device artifacts record the device ids they
were compiled for and are re-loaded onto a DIFFERENT device by remapping
the device assignment at deserialize time — one artifact serves every
replica of a pool. Multi-device (SPMD) artifacts load only onto the same
device set; a different set is a miss (the program's collective schedule
is placement-specific).

Metrics (``metrics.group("compile_cache")``): ``hits`` / ``misses`` /
``stores`` / ``corrupt_entries`` / ``env_mismatches`` / ``fallbacks`` /
``retarget_loads`` counters and ``load_ms`` / ``compile_ms`` gauges
(last observed; full series under the same-named histories).
"""

from __future__ import annotations

import dataclasses
import hashlib
import io
import os
import pickle
import tempfile
import threading
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

from flinkml_tpu.utils.logging import get_logger
from flinkml_tpu.utils.metrics import metrics

_log = get_logger("compile_cache")

#: Setting this env var to a directory path activates a process-wide
#: disk-backed store lazily (no code changes at the compile sites).
ENV_DIR_VAR = "FLINKML_TPU_COMPILE_CACHE"

_FORMAT = 1

def env_fingerprint() -> Dict[str, str]:
    """The environment half of the artifact key (see module docstring).
    Everything that can change what machine code a compile produces —
    or whether the produced code can legally load."""
    import jax
    import jaxlib

    devs = jax.devices()
    client = devs[0].client
    return {
        "jax": str(jax.__version__),
        "jaxlib": str(jaxlib.__version__),
        "backend": str(jax.default_backend()),
        "device_kind": str(devs[0].device_kind),
        "num_devices": str(len(devs)),
        "platform_version": str(getattr(client, "platform_version", "")),
        "x64": str(bool(jax.config.jax_enable_x64)),
    }


def _env_hash(env: Dict[str, str]) -> str:
    blob = "\x00".join(f"{k}={env[k]}" for k in sorted(env))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def stable_key_repr(key: Any) -> str:
    """A canonical, process-independent rendering of a cache key.

    ``repr`` of a tuple of primitives is already stable, but keys embed
    frozen dataclasses (``ShardingPlan``, ``PrecisionPolicy``) and may
    embed dicts; this renders dataclasses as sorted ``(field, value)``
    pairs and dicts sorted by key, so two processes building the same
    identity always hash to the same artifact."""
    out: list = []

    def walk(v: Any) -> str:
        if dataclasses.is_dataclass(v) and not isinstance(v, type):
            fields = sorted(
                (f.name, getattr(v, f.name)) for f in dataclasses.fields(v)
            )
            inner = ",".join(f"{n}={walk(x)}" for n, x in fields)
            return f"{type(v).__name__}({inner})"
        if isinstance(v, dict):
            inner = ",".join(
                f"{walk(k)}:{walk(v[k])}" for k in sorted(v, key=repr)
            )
            return f"{{{inner}}}"
        if isinstance(v, (tuple, list)):
            return "(" + ",".join(walk(x) for x in v) + ")"
        if isinstance(v, (str, bytes, int, float, bool)) or v is None:
            return repr(v)
        return f"{type(v).__name__}:{v!r}"

    out.append(walk(key))
    return "".join(out)


def _key_hash(key: Any) -> str:
    return hashlib.sha256(stable_key_repr(key).encode()).hexdigest()[:24]


def _load_retargeted(entry: Dict[str, Any], device):
    """Load a single-device artifact onto ``device`` instead of the
    device it was compiled for.

    ``serialize_executable.deserialize_and_load(...,
    execution_devices=[device])`` alone cannot do this on jax 0.9.0: its
    unpickler looks the RECORDED device id up among the execution
    devices (KeyError), and XLA:CPU keeps the compile-time device
    assignment unless the compile options carry the new one. So this
    subclasses jax's own unpickler and overrides exactly those two
    things."""
    import jax
    import numpy as np
    from jax._src.lib import xla_client as xc
    from jax.experimental import serialize_executable as se

    (src,) = entry["device_ids"]

    class _Retarget(se._JaxPjrtUnpickler):
        def persistent_load(self, pid):
            if pid[0] == "exec":
                opts = xc.CompileOptions()
                opts.device_assignment = xc.DeviceAssignment.create(
                    np.asarray([[device.id]], dtype=np.int32)
                )
                return self.backend.deserialize_executable(
                    pid[1], self.execution_devices, opts
                )
            return super().persistent_load(pid)

    unpickler = _Retarget(io.BytesIO(entry["payload"]), device.client,
                          [device])
    unpickler.devices_by_id = {int(src): device}
    unloaded, args_info_flat, no_kwargs = unpickler.load()
    return jax.stages.Compiled(
        unloaded.load(), [], entry["in_tree"].unflatten(args_info_flat),
        entry["out_tree"], no_kwargs=no_kwargs,
    )


class CompileCacheStore:
    """Disk-backed (or memory-only) AOT artifact store.

    ``directory=None`` is a process-local store: artifacts live in
    memory only — no persistence, but N replicas in one process still
    share one compile. With a directory, artifacts additionally persist
    under ``<directory>/<env_hash>/`` and a FRESH process's compile
    sites become disk reads.
    """

    def __init__(self, directory: Optional[str] = None):
        self.directory = os.path.abspath(directory) if directory else None
        self._metrics = metrics.group("compile_cache")
        self._lock = threading.Lock()
        self._key_locks: Dict[str, threading.Lock] = {}
        # key hash -> entry dict (payload + trees + device ids). For a
        # MEMORY-ONLY store this is the storage itself (what lets pool
        # replicas share one compile without a cache directory); a
        # disk-backed store leaves it empty and re-reads entries from
        # disk per consumer, so executable bytes are never pinned in
        # RAM twice (call sites cache the loaded programs).
        self._memory: Dict[str, Dict[str, Any]] = {}
        self._env: Optional[Dict[str, str]] = None

    # -- plumbing ----------------------------------------------------------
    def _environment(self) -> Dict[str, str]:
        if self._env is None:
            self._env = env_fingerprint()
        return self._env

    def _key_lock(self, khash: str) -> threading.Lock:
        with self._lock:
            lock = self._key_locks.get(khash)
            if lock is None:
                lock = self._key_locks[khash] = threading.Lock()
            return lock

    def drop_memory(self) -> None:
        """Drop the in-process artifact layer (compile-counting tests
        want a clean slate); on-disk artifacts survive."""
        with self._lock:
            self._memory.clear()

    def entry_path(self, key: Any) -> Optional[str]:
        """The on-disk path ``key``'s artifact lives at (None for a
        memory-only store). Exists only after a successful store."""
        if self.directory is None:
            return None
        env_dir = os.path.join(self.directory,
                               _env_hash(self._environment()))
        return os.path.join(env_dir, f"{_key_hash(key)}.aot")

    # -- serialize / deserialize -------------------------------------------
    def _serialize(self, compiled, key: Any,
                   device_ids: Sequence[int]) -> Optional[Dict[str, Any]]:
        from jax.experimental import serialize_executable as se

        try:
            payload, in_tree, out_tree = se.serialize(compiled)
        except Exception as e:  # noqa: BLE001 — degrade, never crash
            self._metrics.counter("fallbacks")
            _log.warning(
                "AOT serialization failed for %s (%s: %s); this program "
                "stays in-memory only",
                stable_key_repr(key)[:120], type(e).__name__, e,
            )
            return None
        return {
            "format": _FORMAT,
            "env": dict(self._environment()),
            "key": stable_key_repr(key),
            "device_ids": [int(i) for i in device_ids],
            "payload": payload,
            "in_tree": in_tree,
            "out_tree": out_tree,
            "sha256": hashlib.sha256(payload).hexdigest(),
        }

    def _load_entry(self, entry: Dict[str, Any],
                    device_ids: Optional[Sequence[int]]):
        """Deserialize an artifact entry into a callable
        ``jax.stages.Compiled``, retargeting single-device programs onto
        ``device_ids`` when they differ from the recorded ids. Returns
        None when the entry cannot serve this placement."""
        import jax
        from jax.experimental import serialize_executable as se

        src = [int(i) for i in entry["device_ids"]]
        dst = src if device_ids is None else [int(i) for i in device_ids]
        by_id = {d.id: d for d in jax.devices()}
        if dst == src:
            return se.deserialize_and_load(
                entry["payload"], entry["in_tree"], entry["out_tree"],
                execution_devices=[by_id[i] for i in src] or None,
            )
        if len(src) != 1 or len(dst) != 1:
            # An SPMD executable's collective schedule is baked for one
            # device set; retargeting is single-device only.
            return None
        program = _load_retargeted(entry, by_id[dst[0]])
        self._metrics.counter("retarget_loads")
        return program

    # -- disk --------------------------------------------------------------
    def _read_disk(self, key: Any) -> Optional[Dict[str, Any]]:
        path = self.entry_path(key)
        if path is None or not os.path.exists(path):
            return None
        try:
            with open(path, "rb") as fh:
                entry = pickle.load(fh)
            if not isinstance(entry, dict) or entry.get("format") != _FORMAT:
                raise ValueError(f"bad entry format {type(entry).__name__}")
            digest = hashlib.sha256(entry["payload"]).hexdigest()
            if digest != entry["sha256"]:
                raise ValueError("payload sha256 mismatch (bit rot?)")
        except Exception as e:  # noqa: BLE001 — corrupt entry: loud miss
            self._metrics.counter("corrupt_entries")
            _log.warning(
                "corrupt compile-cache entry %s (%s: %s); deleting it and "
                "recompiling fresh", path, type(e).__name__, e,
            )
            try:
                os.unlink(path)
            except OSError:
                pass
            return None
        if entry.get("env") != self._environment():
            # A copied-in artifact from another environment: the path
            # hash should already have missed, but the embedded env is
            # the second line of defense.
            self._metrics.counter("env_mismatches")
            _log.warning(
                "compile-cache entry %s was built for a different "
                "environment (%s); ignoring it", path, entry.get("env"),
            )
            return None
        return entry

    def _write_disk(self, key: Any, entry: Dict[str, Any]) -> None:
        path = self.entry_path(key)
        if path is None:
            return
        env_dir = os.path.dirname(path)
        try:
            os.makedirs(env_dir, exist_ok=True)
            env_json = os.path.join(env_dir, "ENV.json")
            if not os.path.exists(env_json):
                import json

                with open(env_json + ".tmp", "w") as fh:
                    json.dump(entry["env"], fh, indent=2, sort_keys=True)
                os.replace(env_json + ".tmp", env_json)
            # Temp file + atomic rename (the CheckpointManager idiom):
            # a concurrent writer or a kill mid-write can never publish
            # a torn entry.
            fd, tmp = tempfile.mkstemp(dir=env_dir, prefix=".tmp-aot-")
            try:
                with os.fdopen(fd, "wb") as fh:
                    pickle.dump(entry, fh, protocol=pickle.HIGHEST_PROTOCOL)
                os.replace(tmp, path)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
            self._metrics.counter("stores")
        except Exception as e:  # noqa: BLE001 — persistence is best-effort
            self._metrics.counter("fallbacks")
            _log.warning(
                "could not persist compile-cache entry %s (%s: %s); the "
                "program stays in-memory only", path, type(e).__name__, e,
            )

    # -- the public entry point --------------------------------------------
    def get_or_compile(
        self,
        key: Any,
        build: Callable[[], Any],
        device_ids: Optional[Sequence[int]] = None,
    ) -> Tuple[Any, str]:
        """Load ``key``'s artifact (memory, then disk) or ``build()`` it.

        ``build`` must return a ``jax.stages.Compiled`` (i.e.
        ``jit(f).lower(*args).compile()``). ``device_ids`` is the
        placement the returned program must execute on — recorded at
        store time, retarget-matched at load time. Returns ``(program,
        outcome)`` with outcome one of ``"memory"``, ``"disk"``,
        ``"compiled"``, ``"uncached"`` (serialization failed; the
        program came from ``build`` and was not stored).
        """
        khash = _key_hash(key)
        with self._key_lock(khash):
            outcome = "memory"
            with self._lock:
                entry = self._memory.get(khash)
            if entry is None:
                entry = self._read_disk(key)
                outcome = "disk"
            if entry is not None:
                t0 = time.perf_counter()
                try:
                    program = self._load_entry(entry, device_ids)
                except Exception as e:  # noqa: BLE001 — loud fallback
                    self._metrics.counter("corrupt_entries")
                    _log.warning(
                        "loading compile-cache artifact for %s failed "
                        "(%s: %s); recompiling fresh",
                        stable_key_repr(key)[:120], type(e).__name__, e,
                    )
                    program = None
                if program is not None:
                    load_ms = (time.perf_counter() - t0) * 1000.0
                    self._metrics.counter("hits")
                    self._metrics.gauge("load_ms", load_ms)
                    self._metrics.record("load_ms", load_ms)
                    if self.directory is None:
                        # Memory-ONLY stores keep the entry — it IS the
                        # storage. Disk-backed stores re-read on the
                        # next in-process consumer instead of pinning a
                        # second copy of every executable's bytes in
                        # RAM for the process lifetime (call sites
                        # cache the LOADED program already).
                        with self._lock:
                            self._memory[khash] = entry
                    return program, outcome
            self._metrics.counter("misses")
            t0 = time.perf_counter()
            program = self._build_fresh(build)
            compile_ms = (time.perf_counter() - t0) * 1000.0
            self._metrics.gauge("compile_ms", compile_ms)
            self._metrics.record("compile_ms", compile_ms)
            entry = self._serialize(
                program, key,
                device_ids if device_ids is not None else (),
            )
            if entry is not None and not self._verify_entry(entry,
                                                            device_ids, key):
                entry = None
            if entry is None:
                return program, "uncached"
            if self.directory is None:
                with self._lock:
                    self._memory[khash] = entry
            self._write_disk(key, entry)
            return program, "compiled"

    @staticmethod
    def _build_fresh(build: Callable[[], Any]):
        """Run ``build``; on the CPU backend, with jax's persistent
        compilation cache suspended: an executable XLA:CPU LOADS from
        that cache re-serializes without its jit-compiled functions
        (jax 0.9.0: the artifact loads, then fails at execution with
        "Function ... not found"), so an artifact must come from a fresh
        backend compile. A TPU executable loaded from the cache
        re-serializes whole (chip run, PR 21), so there the cache stays
        on and a warm process's first replica hits it."""
        import jax

        from flinkml_tpu.utils import jax_cache

        if jax.default_backend() != "cpu":
            return build()
        with jax_cache.suspended():
            return build()

    def _verify_entry(self, entry: Dict[str, Any],
                      device_ids: Optional[Sequence[int]],
                      key: Any) -> bool:
        """Prove the just-serialized artifact actually loads BEFORE
        persisting it — a backend whose serialization is lossy (the
        symbol-stripping failure above, or any future one) degrades to
        compile-only instead of planting artifacts that poison every
        later cold start."""
        try:
            self._load_entry(entry, device_ids)
            return True
        except Exception as e:  # noqa: BLE001 — refuse to persist
            self._metrics.counter("fallbacks")
            _log.warning(
                "AOT artifact for %s failed its post-serialize load "
                "check (%s: %s); not persisting it",
                stable_key_repr(key)[:120], type(e).__name__, e,
            )
            return False


# -- the process-wide active store -------------------------------------------

_ACTIVE: list = [None]
_CONFIGURED = [False]  # explicit configure() beats the env var


def configure(store: "CompileCacheStore | str | None") -> Optional[
        CompileCacheStore]:
    """Install the process-wide store: a :class:`CompileCacheStore`, a
    directory path, or None (disable — every compile site reverts to
    plain in-memory jit). Returns the installed store."""
    if isinstance(store, str):
        store = CompileCacheStore(store)
    _ACTIVE[0] = store
    _CONFIGURED[0] = True
    return store


def active_store() -> Optional[CompileCacheStore]:
    """The process-wide store the compile sites consult: whatever
    :func:`configure` installed, else a disk store at
    ``$FLINKML_TPU_COMPILE_CACHE`` (created lazily), else None."""
    if _CONFIGURED[0]:
        return _ACTIVE[0]
    directory = os.environ.get(ENV_DIR_VAR)
    if directory:
        _ACTIVE[0] = CompileCacheStore(directory)
        _CONFIGURED[0] = True
        return _ACTIVE[0]
    return _ACTIVE[0]


def ensure_store() -> CompileCacheStore:
    """The active store, creating a process-local (memory-only) one when
    nothing is configured — what :class:`~flinkml_tpu.serving.pool
    .ReplicaPool` calls at spin-up so N replicas share one compile even
    without a cache directory."""
    store = active_store()
    if store is None:
        store = CompileCacheStore(None)
        _ACTIVE[0] = store
        _CONFIGURED[0] = True
    return store


def reset() -> None:
    """Forget the process-wide store AND re-arm the env-var lookup
    (tests)."""
    _ACTIVE[0] = None
    _CONFIGURED[0] = False
