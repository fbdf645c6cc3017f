"""Pass 3 — transfer/retrace guard for hot loops.

The fused executor's performance contract is: after warmup, a hot
``transform`` loop costs **zero** compiles (the shape-bucketed cache
serves every row count in a bucket) and no surprise host↔device traffic.
Nothing enforced that contract at runtime — a fingerprint regression or a
stage silently falling back to the host path would only show up as a
latency cliff in production.

:class:`TransferRetraceGuard` instruments the region it wraps:

  - **compiles** (FML402): every fused-cache compile inside the region is
    checked against the bucket policy. A compile whose chain (cache key
    minus the bucket component) was already compiled — before or inside
    the region — is a legitimate *new-bucket* compile and is allowed by
    default (``allow_new_buckets``). Any other compile counts against
    ``allow_compiles`` (default 0: warm up before entering the guard).
  - **cache aliasing** (FML403): two in-region compiles with identical
    input specs and bucket but different chain fingerprints indicate an
    unstable fingerprint churning the cache.
  - **transfers** (FML401): deltas of the ``pipeline.fusion``
    host→device counters and the ``table`` device→host materialization
    counters, checked against declared budgets (``None`` = unchecked).

Use as a context manager (raises :class:`GuardViolation` listing the
findings) or with ``raise_on_violation=False`` and read ``.findings``.
The pytest marker ``@pytest.mark.no_retrace`` (see ``tests/conftest.py``)
wraps a test in this guard.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from flinkml_tpu.analysis.findings import Finding


class GuardViolation(AssertionError):
    """Raised when a guarded region breaks its transfer/retrace budget."""

    def __init__(self, findings: List[Finding]):
        self.findings = list(findings)
        super().__init__(
            "transfer/retrace guard violated:\n"
            + "\n".join(f.render() for f in self.findings)
        )


def _chain_identity(key: Tuple) -> Tuple:
    """A fused program's key minus its row bucket (index 4 of the layout
    ``(chain fp, ext specs, const specs, out names, bucket, policy)``):
    the identity under which a compile at a NEW bucket is
    policy-allowed. The precision policy STAYS in the identity —
    flipping it compiles a genuinely different program. What a
    fused-CACHE key carries after those six elements (the placement,
    once a compile-cache store is active: any process that has started a
    ReplicaPool) is not the program's: ``on_compile`` reports the
    program key alone."""
    return key[:4] + key[5:6]


def _counters(group: str) -> Dict[str, float]:
    from flinkml_tpu.utils.metrics import metrics

    return dict(metrics.group(group).snapshot()["counters"])


class TransferRetraceGuard:
    """Budget-checked instrumentation of a fused-execution region."""

    def __init__(
        self,
        allow_compiles: int = 0,
        allow_new_buckets: bool = True,
        allow_host_to_device: Optional[int] = None,
        allow_device_to_host: Optional[int] = None,
        raise_on_violation: bool = True,
        location: Optional[str] = None,
    ):
        self.allow_compiles = int(allow_compiles)
        self.allow_new_buckets = bool(allow_new_buckets)
        self.allow_host_to_device = allow_host_to_device
        self.allow_device_to_host = allow_device_to_host
        self.raise_on_violation = bool(raise_on_violation)
        self.location = location
        self.findings: List[Finding] = []
        self._compiled_keys: List[Tuple] = []

    # -- region lifecycle --------------------------------------------------
    def __enter__(self) -> "TransferRetraceGuard":
        from flinkml_tpu import pipeline_fusion

        self._fusion_before = _counters("pipeline.fusion")
        self._table_before = _counters("table")
        # Chains already compiled before the region: compiles for these at
        # NEW buckets are policy-allowed, not retraces.
        with pipeline_fusion._LOCK:
            self._known_chains = {
                _chain_identity(k) for k in pipeline_fusion._CACHE
                if "__specs__" not in k
            }
        self._compiled_keys = []
        pipeline_fusion.on_compile.append(self._compiled_keys.append)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        from flinkml_tpu import pipeline_fusion

        try:
            pipeline_fusion.on_compile.remove(self._compiled_keys.append)
        except ValueError:
            # A test hook reset on_compile inside the region; fine.
            pass
        self.findings = self._evaluate()
        if exc_type is None and self.findings and self.raise_on_violation:
            raise GuardViolation(self.findings)
        return False

    # -- evaluation --------------------------------------------------------
    def _evaluate(self) -> List[Finding]:
        findings: List[Finding] = []

        # Compile policy. Key layout (pipeline_fusion._run_program):
        # (chain fingerprint, ext specs, const specs, out names, bucket,
        # precision policy).
        counted = 0
        seen_chains = set(self._known_chains)
        # Fingerprint-churn detection: keyed by everything EXCEPT the
        # chain fingerprint. Two legitimately different chains almost
        # always differ in const specs or output names too; an unstable
        # fingerprint differs ONLY in the fingerprint, every call —
        # requiring 3+ distinct fingerprints keeps a deliberate pair of
        # alternative chains (budgeted via allow_compiles) unflagged.
        by_shape: Dict[Tuple, set] = {}
        for key in self._compiled_keys:
            chain_fp, ext_specs, consts, outs, bucket, policy = key
            by_shape.setdefault(
                (ext_specs, consts, outs, bucket, policy), set()
            ).add(chain_fp)
        for (_ext, _consts, _outs, bucket, _pol), fps in by_shape.items():
            if len(fps) >= 3:
                findings.append(Finding(
                    "FML403",
                    f"{len(fps)} compiles share input/constant specs, "
                    f"outputs, and bucket {bucket} but differ only in "
                    "chain fingerprint — an unstable fingerprint is "
                    "churning the compile cache",
                    location=self.location,
                    fix_hint="make transform_kernel fingerprints a pure "
                             "function of stage config",
                ))
        for key in self._compiled_keys:
            chain = _chain_identity(key)
            # The identity is bucket-independent (but policy-INCLUSIVE:
            # a policy flip is a genuinely new program), so a chain seen
            # at ANY bucket (pre-region cache or earlier in-region
            # compile) makes this a new-bucket compile of a known chain.
            if chain in seen_chains:
                if not self.allow_new_buckets:
                    counted += 1
            else:
                counted += 1
                seen_chains.add(chain)
        if counted > self.allow_compiles:
            findings.append(Finding(
                "FML402",
                f"{counted} compile(s) of new chains in a guarded region "
                f"(budget {self.allow_compiles}) — a hot loop retraced "
                "beyond the declared bucket policy",
                location=self.location,
                fix_hint="warm the chain up before the guarded region, or "
                         "raise allow_compiles if new chains are expected",
            ))

        fusion_after = _counters("pipeline.fusion")
        table_after = _counters("table")

        def delta(before, after, key):
            return after.get(key, 0.0) - before.get(key, 0.0)

        if self.allow_host_to_device is not None:
            h2d = delta(self._fusion_before, fusion_after,
                        "host_to_device_transfers")
            if h2d > self.allow_host_to_device:
                findings.append(Finding(
                    "FML401",
                    f"{int(h2d)} host->device transfer(s) in a guarded "
                    f"region (budget {self.allow_host_to_device})",
                    location=self.location,
                    fix_hint="keep hot-loop inputs device-resident "
                             "(reuse the same Table; fused outputs stay "
                             "on device)",
                ))
        if self.allow_device_to_host is not None:
            d2h = delta(self._table_before, table_after,
                        "device_to_host_materializations")
            if d2h > self.allow_device_to_host:
                findings.append(Finding(
                    "FML401",
                    f"{int(d2h)} device->host materialization(s) in a "
                    f"guarded region (budget {self.allow_device_to_host})",
                    location=self.location,
                    fix_hint="an intermediate is being read back to host "
                             "inside the loop — read results once outside, "
                             "or budget the reads explicitly",
                ))
        return findings


def transfer_retrace_guard(**kwargs) -> TransferRetraceGuard:
    """Convenience alias: ``with transfer_retrace_guard(...):``."""
    return TransferRetraceGuard(**kwargs)
