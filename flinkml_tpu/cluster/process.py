"""Spawning and supervising worker child processes.

:class:`WorkerSpec` is everything a child needs to stand up its engine,
pickled to a file the child's ``main`` reads (models ride as their own
pickle blob so a registry-backed worker can instead open the registry
directory itself). :class:`WorkerProcess` spawns
``python -m flinkml_tpu.cluster.worker``, pins the child's device slice
via env (``XLA_FLAGS --xla_force_host_platform_device_count`` on the
CPU mesh — each worker owns its OWN XLA executor pool and its own GIL,
which is the entire point of the subsystem), points it at the shared
compile-cache directory, and waits for the single JSON ready line on
the child's stdout. ``spawn_ms`` is recorded for the ``cluster.*``
metrics group; a child that exits or stays silent past the deadline is
a typed :class:`~flinkml_tpu.cluster.errors.WorkerSpawnError` with the
tail of the child's stderr attached (the stuck-worker runbook's first
artifact — see ``docs/development/cluster.md``).
"""

from __future__ import annotations

import dataclasses
import json
import os
import pickle
import select
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, Mapping, Optional, Sequence

from flinkml_tpu.cluster.errors import WorkerSpawnError
from flinkml_tpu.utils.logging import get_logger

_log = get_logger("cluster.process")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))


@dataclasses.dataclass
class WorkerSpec:
    """The child's construction record (see module docstring)."""

    example: Dict[str, Any]                 # column name -> host array
    source: Dict[str, Any]                  # {"kind": "model"|"registry", ...}
    config: Dict[str, Any] = dataclasses.field(default_factory=dict)
    output_cols: Optional[Sequence[str]] = None
    name: str = "worker"
    compile_cache_dir: Optional[str] = None
    max_payload: Optional[int] = None

    @classmethod
    def for_model(cls, model: Any, example_columns: Dict[str, Any],
                  **kw) -> "WorkerSpec":
        return cls(
            example=dict(example_columns),
            source={"kind": "model", "blob": pickle.dumps(model, protocol=5)},
            **kw,
        )

    @classmethod
    def for_registry(cls, root: str, example_columns: Dict[str, Any],
                     **kw) -> "WorkerSpec":
        return cls(
            example=dict(example_columns),
            source={"kind": "registry", "root": os.path.abspath(root)},
            **kw,
        )

    def write(self, path: str) -> str:
        with open(path, "wb") as f:
            pickle.dump(dataclasses.asdict(self), f, protocol=5)
        return path


class WorkerProcess:
    """One supervised worker child."""

    def __init__(self, spec: WorkerSpec, *,
                 name: Optional[str] = None,
                 devices_per_worker: Optional[int] = 1,
                 env: Optional[Mapping[str, str]] = None,
                 spawn_timeout_s: float = 180.0,
                 python: str = sys.executable,
                 workdir: Optional[str] = None):
        self.spec = spec
        self.name = name or spec.name
        self.devices_per_worker = devices_per_worker
        self._extra_env = dict(env or {})
        self.spawn_timeout_s = float(spawn_timeout_s)
        self.python = python
        safe = self.name.replace("/", "-").replace(os.sep, "-")
        self._workdir = workdir or tempfile.mkdtemp(
            prefix=f"flinkml-worker-{safe}-"
        )
        self._proc: Optional[subprocess.Popen] = None
        self.host = "127.0.0.1"
        self.port: Optional[int] = None
        self.pid: Optional[int] = None
        self.spawn_ms: Optional[float] = None
        self.stderr_path = os.path.join(self._workdir, "stderr.log")

    @property
    def workdir(self) -> str:
        """The child's scratch directory (spec file, stderr log)."""
        return self._workdir

    # -- lifecycle ---------------------------------------------------------
    def spawn(self) -> "WorkerProcess":
        """Start the child and block until its ready line (or raise
        :class:`WorkerSpawnError` with the stderr tail)."""
        t0 = time.monotonic()
        spec_path = self.spec.write(
            os.path.join(self._workdir, "spec.pkl")
        )
        env = dict(os.environ)
        # Workers are virtual-CPU-device processes, whatever the parent
        # runs on (ClusterPool refuses a non-CPU parent outright).
        env["JAX_PLATFORMS"] = "cpu"
        if self.devices_per_worker is not None:
            # The child's device slice: its OWN virtual-device count,
            # not the parent's (a worker is its own XLA world).
            env["XLA_FLAGS"] = _replace_device_count_flag(
                env.get("XLA_FLAGS", ""), int(self.devices_per_worker)
            )
        env["PYTHONPATH"] = os.pathsep.join(
            x for x in (_REPO_ROOT, env.get("PYTHONPATH")) if x
        )
        env.update(self._extra_env)
        stderr = open(self.stderr_path, "ab")
        try:
            self._proc = subprocess.Popen(
                [self.python, "-m", "flinkml_tpu.cluster.worker",
                 spec_path],
                stdout=subprocess.PIPE, stderr=stderr, env=env,
            )
        finally:
            stderr.close()
        ready = self._await_ready(t0)
        self.port = int(ready["port"])
        self.pid = int(ready["pid"])
        self.spawn_ms = (time.monotonic() - t0) * 1000.0
        _log.info("worker %s up: pid %d port %d in %.0f ms "
                  "(engine stage %.0f ms)", self.name, self.pid,
                  self.port, self.spawn_ms,
                  ready.get("spawn_stage_ms", -1.0))
        return self

    def _await_ready(self, t0: float) -> Dict[str, Any]:
        assert self._proc is not None and self._proc.stdout is not None
        deadline = t0 + self.spawn_timeout_s
        out = self._proc.stdout
        line = b""
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                self.kill()
                raise WorkerSpawnError(
                    f"worker {self.name} produced no ready line within "
                    f"{self.spawn_timeout_s}s; stderr tail:\n"
                    f"{self._stderr_tail()}"
                )
            if self._proc.poll() is not None:
                raise WorkerSpawnError(
                    f"worker {self.name} exited rc={self._proc.returncode} "
                    f"during startup; stderr tail:\n{self._stderr_tail()}"
                )
            rl, _, _ = select.select([out], [], [], min(0.25, remaining))
            if not rl:
                continue
            line = out.readline()
            if not line:
                continue
            try:
                ready = json.loads(line)
            except ValueError:
                continue  # stray stdout noise; keep waiting for ours
            if ready.get("ready"):
                return ready

    def _stderr_tail(self, n: int = 2000) -> str:
        try:
            with open(self.stderr_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(0, size - n))
                return f.read().decode("utf-8", "replace")
        except OSError:
            return "<no stderr captured>"

    @property
    def alive(self) -> bool:
        return self._proc is not None and self._proc.poll() is None

    @property
    def returncode(self) -> Optional[int]:
        return None if self._proc is None else self._proc.poll()

    def terminate(self) -> None:
        if self._proc is not None and self._proc.poll() is None:
            self._proc.terminate()

    def kill(self) -> None:
        if self._proc is not None and self._proc.poll() is None:
            self._proc.kill()

    def join(self, timeout_s: Optional[float] = 10.0) -> Optional[int]:
        if self._proc is None:
            return None
        try:
            return self._proc.wait(timeout_s)
        except subprocess.TimeoutExpired:
            return None


def _replace_device_count_flag(flags: str, count: int) -> str:
    """Set ``--xla_force_host_platform_device_count=count`` in an
    ``XLA_FLAGS`` string, replacing any inherited value (the parent's
    virtual-device count is about the PARENT's mesh)."""
    kept = [
        t for t in flags.split()
        if not t.startswith("--xla_force_host_platform_device_count")
    ]
    kept.append(f"--xla_force_host_platform_device_count={int(count)}")
    return " ".join(kept)
