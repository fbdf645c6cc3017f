"""Shared compile-on-demand loader for the native (C++) ingest parsers.

Each parser lives in ``flinkml_tpu/native/<name>.cpp`` with a C ABI (the
sources ship inside the wheel via package-data); the first import compiles
it with the system ``g++`` into a ``build/`` dir next to the sources — or,
when the installed package is read-only, into a per-user cache dir —
(atomic rename so concurrent processes never dlopen a half-written file)
and caches the handle. The artifact is named by a hash of the source and
the compiler flags, so only a library built from exactly the committed
``.cpp`` can load; a stale or copied-in ``.so`` is never trusted.
Callers fall back to pure Python when no compiler is available — the
native path is a throughput optimization, never a functional
requirement — and the fallback is logged once at WARNING.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading
from typing import Callable, Dict, Optional

from flinkml_tpu.utils.logging import get_logger

_log = get_logger("io.native")

_COMPILE = ("g++", "-O3", "-std=c++17", "-shared", "-fPIC")
_LINK = ("-lpthread",)

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "native",
)


def _build_dir() -> str:
    preferred = os.path.join(_NATIVE_DIR, "build")
    try:
        os.makedirs(preferred, exist_ok=True)
        if os.access(preferred, os.W_OK):
            return preferred
    except OSError:
        pass
    fallback = os.path.join(
        os.environ.get("XDG_CACHE_HOME", tempfile.gettempdir()),
        "flinkml_tpu_native",
    )
    os.makedirs(fallback, exist_ok=True)
    return fallback



def artifact_path(name: str) -> str:
    """Where ``<name>.cpp``'s library lives: ``<build dir>/<name>-<sha256
    of source + compile command>.so``."""
    digest = hashlib.sha256(" ".join(_COMPILE + _LINK).encode())
    with open(os.path.join(_NATIVE_DIR, f"{name}.cpp"), "rb") as fh:
        digest.update(fh.read())
    return os.path.join(_build_dir(), f"{name}-{digest.hexdigest()[:16]}.so")


_lock = threading.Lock()
_cache: Dict[str, Optional[ctypes.CDLL]] = {}


def compile_and_load(
    name: str, declare: Callable[[ctypes.CDLL], None]
) -> Optional[ctypes.CDLL]:
    """Compile ``flinkml_tpu/native/<name>.cpp`` (unless its artifact
    exists) and load it.

    ``declare`` sets restype/argtypes on the fresh handle. Returns None if
    compilation or loading fails (callers use their Python fallback);
    the failure is logged and cached so we do not retry per call.
    """
    with _lock:
        if name in _cache:
            return _cache[name]
        src = os.path.join(_NATIVE_DIR, f"{name}.cpp")
        try:
            so = artifact_path(name)
            if not os.path.exists(so):
                tmp_so = f"{so}.tmp.{os.getpid()}"
                subprocess.run(
                    [*_COMPILE, "-o", tmp_so, src, *_LINK],
                    check=True,
                    capture_output=True,
                )
                os.replace(tmp_so, so)
            lib = ctypes.CDLL(so)
            declare(lib)
            _cache[name] = lib
        except (OSError, subprocess.CalledProcessError) as e:
            detail = getattr(e, "stderr", b"") or b""
            _log.warning(
                "native parser %s unavailable (%s: %s %s); using the "
                "pure-Python parser", name, type(e).__name__, e,
                detail.decode(errors="replace")[-500:],
            )
            _cache[name] = None
        return _cache[name]
