"""Persistent AOT compile cache — zero-cold-start execution.

Every process today pays full trace+compile on spin-up even though the
programs it builds are identified by frozen, hashable, collision-tested
cache keys (the fused executor's chain/bucket/policy key, the serving
warmup's per-bucket keys, the plan-sharded step's lru key). This package
turns those identities into *persistent artifacts*: a compiled XLA
executable is serialized once (``jax.experimental.serialize_executable``,
the AOT half of ``jax.export``) and every later process — a fresh
replica, a rolling swap, an elastic reshard restart — loads it from disk
instead of recompiling, so time-to-first-prediction is I/O-bound.

See :mod:`flinkml_tpu.compile_cache.store` for the key schema,
invalidation rules, and the fallback ladder, and
``docs/development/compile_cache.md`` for the operator runbook.
"""

from flinkml_tpu.compile_cache.store import (  # noqa: F401
    CompileCacheStore,
    ENV_DIR_VAR,
    active_store,
    configure,
    ensure_store,
    env_fingerprint,
    reset,
    stable_key_repr,
)

__all__ = [
    "CompileCacheStore",
    "ENV_DIR_VAR",
    "active_store",
    "configure",
    "ensure_store",
    "env_fingerprint",
    "reset",
    "stable_key_repr",
]
