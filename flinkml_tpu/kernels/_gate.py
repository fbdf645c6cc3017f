"""The kernel-backend gate — a measured default for the choice between
XLA's lowering and the hand-written Pallas kernels.

Two *sites* exist, one per gated inner loop:

- ``fused_chain``  — the fused pipeline executor's per-bucket chain
  program (:mod:`flinkml_tpu.kernels.chain`),
- ``segment_sum``  — the padded-ELL sparse gradient scatter-accumulate
  shared by the linear SGD trainers, ``BatchedCSR.rmatvec``, and the
  Word2Vec embedding accumulator (:mod:`flinkml_tpu.kernels.segsum`).

Lookup precedence per site:
``FLINKML_TPU_KERNELS`` env var > the mesh-keyed autotune table's
``kernel_backend_<site>`` knob > the static default ``"xla"``. The env
var takes either one backend for every site (``pallas``/``xla``) or a
per-site list (``fused_chain=pallas,segment_sum=xla``); anything else
raises.

Refusal contract: a Pallas backend selected EXPLICITLY (env var or a
``backend=`` argument) refuses unsupported dtypes/shapes LOUDLY with
:class:`KernelUnsupportedError` — never a silent wrong-numerics
fallback. A Pallas backend that came from the tuning table degrades to
``"xla"`` with one warning (a committed table must never take training
down — the same never-crash discipline as a stale autotune entry).

Resolved backends are cached per (env value, site) — the lru key every
consumer must thread into ITS compile cache: the fused executor's
program key, the trainer factories' ``functools.lru_cache`` keys, and
``jax.jit`` static args all carry the backend, so flipping the gate can
never alias a Pallas program with an XLA one.
"""

from __future__ import annotations

import functools
import os
from typing import Dict, Optional, Tuple

from flinkml_tpu.utils.logging import get_logger

_log = get_logger("kernels")

#: The two gated sites (module docstring).
SITES = ("fused_chain", "segment_sum")

#: Known backends. ``xla`` is the static default everywhere; ``pallas``
#: must win a measured A/B (the autotune ``kernel_backend_*`` knobs) or
#: be asked for explicitly.
BACKENDS = ("xla", "pallas")

#: Env gate: one backend for all sites, or ``site=backend`` pairs.
ENV_VAR = "FLINKML_TPU_KERNELS"

#: Force/forbid interpreter-mode ``pallas_call`` (default: interpret on
#: every non-TPU backend so CPU CI runs the kernels device-free).
ENV_INTERPRET_VAR = "FLINKML_TPU_KERNELS_INTERPRET"

#: The autotune knob family (``kernel_backend_<site>``).
KNOB_PREFIX = "kernel_backend_"

_WARNED: set = set()


class KernelUnsupportedError(ValueError):
    """An explicitly-requested Pallas kernel cannot run this dtype/shape.

    Raised INSTEAD of silently falling back: the caller asked for the
    Pallas backend by name (env var or argument), so degrading quietly
    would misreport what was measured. The message names the site, the
    offending dtype/shape, and the supported set.
    """


@functools.lru_cache(maxsize=64)
def _parse_env(raw: str) -> Dict[str, str]:
    """``FLINKML_TPU_KERNELS`` → ``{site: backend}`` (``"*"`` = every
    site). Raises ``ValueError`` on unknown sites/backends — a typo'd
    gate must fail loudly, not silently select the default."""
    raw = raw.strip()
    if not raw:
        return {}
    if "=" not in raw:
        if raw not in BACKENDS:
            raise ValueError(
                f"{ENV_VAR}={raw!r}: expected one of {BACKENDS} or "
                f"site=backend pairs over sites {SITES}"
            )
        return {"*": raw}
    out: Dict[str, str] = {}
    for pair in raw.split(","):
        site, _, backend = pair.partition("=")
        site, backend = site.strip(), backend.strip()
        if site not in SITES or backend not in BACKENDS:
            raise ValueError(
                f"{ENV_VAR}={raw!r}: bad pair {pair!r} — sites {SITES}, "
                f"backends {BACKENDS}"
            )
        out[site] = backend
    return out


def resolve_backend(site: str) -> Tuple[str, bool]:
    """``(backend, explicit)`` for ``site``: the env var wins (explicit),
    then the current mesh's ``kernel_backend_<site>`` autotune entry
    (not explicit), then ``"xla"``."""
    if site not in SITES:
        raise ValueError(f"unknown kernel site {site!r}; known: {SITES}")
    env = _parse_env(os.environ.get(ENV_VAR, ""))
    chosen = env.get(site, env.get("*"))
    if chosen is not None:
        return chosen, True
    from flinkml_tpu.autotune import tuned_default

    return tuned_default(KNOB_PREFIX + site, "xla", allowed=BACKENDS), False


def backend_for(site: str) -> str:
    """The resolved backend name for ``site`` (gate precedence in the
    module docstring), ignoring per-call support — use the site
    dispatchers for a support-checked choice."""
    return resolve_backend(site)[0]


def interpret_mode() -> bool:
    """Whether ``pallas_call`` should run under the interpreter: yes on
    every non-TPU backend (CPU CI stays device-free), overridable with
    ``FLINKML_TPU_KERNELS_INTERPRET=0/1`` (device runs can force the
    interpreter for a parity bisect)."""
    forced = os.environ.get(ENV_INTERPRET_VAR)
    if forced is not None:
        if forced not in ("0", "1"):
            raise ValueError(
                f"{ENV_INTERPRET_VAR}={forced!r}: expected '0' or '1'"
            )
        return forced == "1"
    import jax

    return jax.default_backend() != "tpu"


#: What tracing a Mosaic kernel imports: Pallas, and the module its TPU
#: lowering pulls in at the first ``pallas_call`` lowered (private: left
#: to that moment where this version of JAX has none of the name).
_TRACING_IMPORTS = ("jax.experimental.pallas", "jax.experimental.pallas.tpu",
                    "jax._src.pallas.mosaic.pallas_call_registration")


def _import_keeping_bytecode(names=_TRACING_IMPORTS) -> None:
    """Import ``names``, their bytecode kept beside the programs this
    process keeps: where JAX's persistent compilation cache is on (the
    entry point's or ``JAX_COMPILATION_CACHE_DIR``'s directory), the
    interpreter reads and writes the modules' compiled code under its
    ``pycache/`` while the imports run (``sys.pycache_prefix``, put back
    after). On a host whose site-packages keep no bytecode the import of
    Pallas is 1.09 s of ``compile()`` in 1.15 (PERF.md section 5), each
    process anew, where the program it traces comes from the cache; a
    process with no cache directory imports as it always did."""
    import importlib
    import os
    import sys

    from flinkml_tpu.utils import jax_cache

    kept = jax_cache.in_use()
    before = sys.pycache_prefix, sys.dont_write_bytecode
    if kept:
        sys.pycache_prefix = os.path.join(kept, "pycache")
        sys.dont_write_bytecode = False
    try:
        for name in names:
            try:
                importlib.import_module(name)
            except ImportError:
                if not name.startswith("jax._src."):
                    raise
    finally:
        sys.pycache_prefix, sys.dont_write_bytecode = before


def import_beside_host_work() -> None:
    """Start importing Pallas on a thread of its own, on a TPU, where it
    is not imported yet: what a fit whose step may hold a Mosaic kernel
    (``sparse_blocks``, ``dense_step``, ``row_update``) calls where it
    starts. A fit's first dispatch traces the kernels, and before it comes
    host work
    that is NumPy's (the plan's pass, the seeded permutation and gather:
    0.8 s at ``lr-criteo``'s 16.8 M rows, 0.7 s at ``lr-a9a``'s 9.4 M);
    the import otherwise stands in the fit between its placement's first
    round and its first step (:func:`_import_keeping_bytecode` has what
    it costs). The import's own lock makes the tracing thread wait for
    what is left of it."""
    import sys
    import threading

    if interpret_mode() or "jax.experimental.pallas" in sys.modules:
        return
    threading.Thread(target=_import_keeping_bytecode, daemon=True).start()


def out_struct(shape, dtype, *operands):
    """The ``out_shape`` entry for a ``pallas_call`` whose output varies
    over the same manual mesh axes as ``operands``: inside
    ``jax.shard_map`` (``check_vma=True``, the default) an output must
    declare its ``vma``; outside one the set is empty."""
    import jax

    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(tuple(shape), dtype, vma=vma)


def refuse_or_fallback(site: str, explicit: bool, reason: str) -> str:
    """The refusal contract: explicit Pallas + unsupported → raise
    :class:`KernelUnsupportedError`; table-chosen Pallas + unsupported
    → one warning, then ``"xla"``."""
    if explicit:
        raise KernelUnsupportedError(
            f"kernels[{site}]: the pallas backend was requested "
            f"explicitly but cannot run here: {reason}. Unset "
            f"{ENV_VAR} (or pass backend='xla') to use the XLA lowering."
        )
    tag = (site, reason)
    if tag not in _WARNED:
        _WARNED.add(tag)
        _log.warning(
            "kernels[%s]: tuning table selected pallas but %s; using the "
            "XLA lowering for this site", site, reason,
        )
    return "xla"


def resolve_checked(site: str, unsupported_reason: Optional[str],
                    backend: Optional[str] = None) -> str:
    """Gate resolution + the support check in one step.

    A ``backend`` argument that merely THREADS THROUGH what the gate
    itself currently resolves (the factory idiom: consumers resolve
    once at fit time and pass the result down as lru-key material)
    inherits the gate's own explicitness — a table-chosen pallas still
    degrades warn-once on unsupported operands instead of crashing the
    consumer. A backend that DISAGREES with the gate is a genuinely
    explicit per-call request and refuses loudly."""
    gate_backend, gate_explicit = resolve_backend(site)
    if backend is None:
        backend, explicit = gate_backend, gate_explicit
    else:
        if backend not in BACKENDS:
            raise ValueError(
                f"backend={backend!r}: expected one of {BACKENDS}"
            )
        explicit = True if backend != gate_backend else gate_explicit
    if backend == "pallas" and unsupported_reason is not None:
        return refuse_or_fallback(site, explicit, unsupported_reason)
    return backend
