"""Fused device-resident pipeline execution.

The per-stage transform path pays, for an N-stage :class:`PipelineModel`,
N host→device uploads, N separate XLA dispatches, and N device→host
downloads — exactly the per-stage materialization the columnar data plane
exists to avoid. This module makes the *pipeline* the unit of compilation:
a run of kernel-capable stages (stages exposing
:meth:`flinkml_tpu.api.AlgoOperator.transform_kernel`) compiles into ONE
``jax.jit`` program; intermediate columns never leave device memory, and
the result :class:`~flinkml_tpu.table.Table` carries device-resident output
columns that materialize to host lazily.

Compile cache and row bucketing
-------------------------------

Programs are cached under a key of

  ``(chain fingerprint, external input col specs, constant specs,
  requested output columns, bucket, policy)``

where the chain fingerprint is the tuple of each kernel's ``fingerprint``,
input col specs are ``(name, dtype, trailing shape)`` of every column the
run reads from the table, constant specs are the shapes/dtypes of each
kernel's model data, and ``bucket`` is the row count padded up to a power
of two (≥ :data:`MIN_ROW_BUCKET`). Padding rows to the bucket plus a
float32 validity mask means one compiled program serves every batch size
within the bucket — repeated ``transform`` calls with differing row counts
cause **zero recompiles** until a call crosses a power-of-two boundary.
Padded rows may compute garbage; the executor slices them off before
returning, and kernels with cross-row reductions apply the mask.

Model data (coefficients, fitted statistics) is passed as *traced
arguments*, so refreshing model data — or loading a different model of the
same shape — reuses the compiled program.

Lazy intermediates (dead-code elimination)
------------------------------------------

A run's eager program returns only its *terminal* columns (those no later
kernel of the run consumes); XLA dead-code-eliminates the rest, so unread
intermediate columns are never even written to memory. Intermediates land
in the result table as :class:`~flinkml_tpu.table.LazyDeviceColumn`: shape
and dtype come from an abstract trace, and the first read executes a
DCE'd program for just that column through the same compile cache. Typical
inference (read the prediction column only) therefore costs one program
that computes nothing it doesn't need.

Precision: programs trace and execute under ``jax.enable_x64``
so kernels reproduce each stage's host-path dtypes exactly (scalers run in
float64 like their numpy transform; predict kernels capture the *ambient*
x64 flag at kernel-build time and cast to the same dtypes ``jnp.asarray``
would give the per-stage path under it). Fused output is bit-identical to
the per-stage path for exactly-rounded ops always, and for everything
under x64 (the framework's test/golden configuration — pinned by the test
suite). The one carve-out: under ambient float32, outputs of
``pin_inputs`` kernels (matmul/transcendental stages) are numerically
equivalent rather than bitwise — f32 matmul reassociation differs between
the bucket-padded fused shape and the exact-row per-stage shape.

Mixed precision (the FML6xx policy gate)
----------------------------------------

An active :class:`~flinkml_tpu.precision.PrecisionPolicy`
(:func:`set_policy` / :func:`precision_scope`; serving threads it via
``ServingConfig.precision``) changes the fused program in exactly the
declared way: every float external input column and every float model
constant is cast to ``policy.compute`` at the program boundary (the
upload stays at storage width; the savings are device-side), the
validity mask is built at ``policy.compute``, and kernel math follows
jax dtype propagation from there. The policy joins BOTH cache keys —
program and abstract-spec — so a bf16 and an f32 program never alias
one executable. Every fresh cache key is validated against the policy
by the FML6xx precision-flow pass
(:mod:`flinkml_tpu.analysis.precision`) BEFORE the program is built:
a chain whose kernels accumulate below ``policy.accum`` (or smuggle a
strong wide constant into the compute region) raises
:class:`~flinkml_tpu.precision.PrecisionValidationError` instead of
compiling. No active policy (the default) leaves every path untouched.

Instrumentation (``metrics.group("pipeline.fusion")``): ``compiles`` /
``cache_hits`` counters, ``fused_segments`` / ``fused_stages``,
``host_to_device_transfers`` / ``host_to_device_bytes``, and
``host_transfer_bytes_avoided`` (bytes of intermediate columns that would
have round-tripped host↔device under per-stage execution). Tests can hook
compilation via :data:`on_compile`.
"""

from __future__ import annotations

import collections
import os
import threading
from typing import Any, Callable, Dict, List, NamedTuple, Sequence, Tuple

import numpy as np

from flinkml_tpu.api import ColumnKernel
from flinkml_tpu.linalg import next_pow2
from flinkml_tpu.table import LazyDeviceColumn, PaddedDeviceColumn, Table
from flinkml_tpu.utils.metrics import metrics
from flinkml_tpu.utils.profiling import named_program, span

#: Smallest row bucket: tiny tables all share one program.
MIN_ROW_BUCKET = 8

#: Callbacks invoked with the cache key whenever a new fused program is
#: compiled (test hook: assert zero retraces across row counts).
on_compile: List[Callable[[Tuple], None]] = []

_CACHE: Dict[Tuple, Callable] = {}
_LOCK = threading.Lock()
_ENABLED = [True]
# Per-THREAD policy slot: a ServingEngine scopes its own dispatcher
# thread's dispatches without clobbering a concurrently-transforming
# trainer thread's ambient policy (and vice versa).
_POLICY = threading.local()


def enabled() -> bool:
    """Fusion master switch: the ``FLINKML_TPU_DISABLE_FUSION=1`` env var or
    :func:`set_enabled` (a test's per-stage baseline) turns the
    fused executor off, restoring pure per-stage execution."""
    return _ENABLED[0] and os.environ.get("FLINKML_TPU_DISABLE_FUSION") != "1"


def set_enabled(flag: bool) -> None:
    _ENABLED[0] = bool(flag)


def active_policy():
    """The :class:`~flinkml_tpu.precision.PrecisionPolicy` fused programs
    compile and validate under on THIS thread (None: plain full-width
    execution). Thread-scoped: each dispatching thread carries its own
    slot, so a serving engine's policy never leaks into a concurrent
    trainer thread's transforms."""
    return getattr(_POLICY, "value", None)


def set_policy(policy) -> None:
    """Install a :class:`~flinkml_tpu.precision.PrecisionPolicy` (object,
    preset name, JSON dict, or None) as THIS thread's fused-executor
    policy. Prefer :func:`precision_scope` for bounded use."""
    from flinkml_tpu.precision import resolve_policy

    _POLICY.value = resolve_policy(policy)


class precision_scope:
    """Context manager scoping an ambient fused-executor policy:

    .. code-block:: python

        with pipeline_fusion.precision_scope("mixed_inference"):
            (out,) = model.transform(table)

    Every fused program compiled inside the scope is FML6xx-validated
    against the policy pre-compile and keyed by it (bf16/f32 programs
    never alias); programs compiled OUTSIDE the scope are untouched and
    untouchable from inside (distinct cache keys). The scope is
    THREAD-scoped (enter/exit on the thread that transforms), so
    concurrent threads — a serving dispatcher beside a training loop —
    never clobber each other's policy."""

    def __init__(self, policy):
        from flinkml_tpu.precision import resolve_policy

        self._policy = resolve_policy(policy)
        self._prev = None

    def __enter__(self):
        self._prev = active_policy()
        _POLICY.value = self._policy
        return self._policy

    def __exit__(self, *exc):
        _POLICY.value = self._prev
        return False


def reset_cache() -> None:
    """Drop every compiled program (tests; never needed in production).
    Also drops the active compile-cache store's in-MEMORY artifact layer
    — compile-counting tests expect a clean slate — while on-disk
    artifacts (the persistent cache) survive."""
    with _LOCK:
        _CACHE.clear()
    with _QUANT_LOCK:
        _QUANT_CONST_CACHE.clear()
    from flinkml_tpu import compile_cache

    store = compile_cache.active_store()
    if store is not None:
        store.drop_memory()


def compiled_program_count() -> int:
    """Number of compiled programs in the cache (shape-spec entries from
    the abstract trace don't count — they cost no compile)."""
    with _LOCK:
        return sum(1 for k in _CACHE if "__specs__" not in k)


def row_bucket(n: int) -> int:
    """Padded row count for ``n`` rows: next power of two, floored at
    :data:`MIN_ROW_BUCKET`."""
    return max(MIN_ROW_BUCKET, next_pow2(n))


class QuantizedConst(NamedTuple):
    """One int8 post-training-quantized model constant as the fused
    program receives it: the per-column absmax-scaled int8 buffer plus
    its float32 scales (:func:`flinkml_tpu.precision.quantize_absmax`).
    A NamedTuple so it rides the constant pytrees through jit/eval_shape
    unchanged; the chain body dequantizes it to ``policy.compute`` width
    in-program, where XLA fuses the two ops into the consumer."""

    q: Any
    scale: Any


def _quant_min_elems() -> int:
    """The int8 tier's minimum-constant-size threshold, with the
    standard gate precedence: explicit ``FLINKML_TPU_INT8_MIN_CONST``
    env var > the mesh-keyed ``int8_min_const_elems`` autotune knob >
    the static default — degraded to the static default on a
    non-numeric/non-positive value (the serving-knob contract: a table
    typo must not take the executor down; a bad EXPLICIT value is
    degraded too, logged by the table layer)."""
    from flinkml_tpu.autotune import tuned_default
    from flinkml_tpu.precision import INT8_MIN_CONST_ELEMS

    env = os.environ.get("FLINKML_TPU_INT8_MIN_CONST")
    if env is not None:
        try:
            v = int(env)
        except ValueError:
            v = 0
        if v >= 1:
            return v
        # An EXPLICIT-but-invalid override degrades to the STATIC
        # default (never silently to the table's value — that would be
        # a third party neither the operator nor the docs named),
        # logged once.
        if env not in _QUANT_ENV_WARNED:
            _QUANT_ENV_WARNED.add(env)
            from flinkml_tpu.utils.logging import get_logger

            get_logger("pipeline.fusion").warning(
                "FLINKML_TPU_INT8_MIN_CONST=%r is not a positive "
                "integer; using the static default %d",
                env, INT8_MIN_CONST_ELEMS,
            )
        return INT8_MIN_CONST_ELEMS
    try:
        v = int(tuned_default("int8_min_const_elems", INT8_MIN_CONST_ELEMS))
    except (TypeError, ValueError):
        return INT8_MIN_CONST_ELEMS
    return v if v >= 1 else INT8_MIN_CONST_ELEMS


_QUANT_ENV_WARNED: set = set()

# Quantized-constant memo: model constants are immutable per fitted
# model, but execute_kernel_chain runs per DISPATCH — re-running the
# absmax passes (abs/max/divide/rint/clip over every weight) on the
# serving hot path would tax exactly the tier sold as a bandwidth
# optimization. Keyed by the host array's identity (the strong ref in
# the value pins the object alive, so an id can never be reused while
# its entry exists); a refreshed model is a NEW array object and
# misses. Bounded TRUE LRU (hits refresh recency) — a hot model's
# constants stay resident while old models' entries (and their device
# buffers) age out.
_QUANT_CONST_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_QUANT_CONST_MAX = 128
_QUANT_LOCK = threading.Lock()


def warmup_transform(
    model,
    example: Table,
    row_counts: Sequence[int],
    output_cols: Sequence[str] = (),
) -> Tuple[List[int], Tuple[str, ...]]:
    """Precompile ``model.transform``'s fused programs for every row
    bucket covering ``row_counts``, so a latency-sensitive caller (the
    serving engine's load path) pays every compile up front and steady
    state is zero-retrace.

    ``example`` supplies the input schema: its host columns are tiled
    row-cyclically to each bucket's exact row count and pushed through
    the real ``transform`` path — the same cache keys production traffic
    will hit (same column specs, same constant specs, same requested
    outputs). ``output_cols`` (default: every column ``transform`` adds)
    are materialized to host afterwards, forcing any lazy-column program
    the caller will read. Returns ``(buckets, read_cols)`` — the sorted
    buckets warmed and the output columns read (the requested ones, or
    the discovered added columns: callers that defaulted ``output_cols``
    learn the schema without paying another transform).
    """
    buckets = sorted({row_bucket(int(n)) for n in row_counts})
    host_cols = {name: np.asarray(example.column(name))
                 for name in example.column_names}
    read = tuple(output_cols)
    for bucket in buckets:
        tiled = Table({
            name: np.resize(col, (bucket,) + col.shape[1:])
            for name, col in host_cols.items()
        })
        (out,) = model.transform(tiled)
        if not read:
            read = tuple(
                c for c in out.column_names if c not in example.column_names
            )
        for c in read:
            out.column(c)
    return buckets, read


def _dense_in_table(table: Table, name: str) -> bool:
    """Whether ``name`` is a column the executor can place on device."""
    if name not in table:
        return False
    if table.is_device_resident(name):
        return True
    return table.column(name).dtype.kind in "fiub"


def collect_run(table: Table, stages: Sequence, start: int):
    """Longest run of kernel-capable stages beginning at ``stages[start]``
    whose external inputs are dense columns of ``table`` (or products of
    earlier kernels in the run). Returns ``(kernels, next_index)`` —
    ``kernels`` empty when ``stages[start]`` cannot join a run."""
    kernels: List[ColumnKernel] = []
    produced: set = set()
    i = start
    while i < len(stages):
        kernel = stages[i].transform_kernel()
        if kernel is None:
            break
        if any(
            c not in produced and not _dense_in_table(table, c)
            for c in kernel.input_cols
        ):
            break
        kernels.append(kernel)
        produced.update(kernel.output_cols)
        i += 1
    return kernels, i


def external_inputs(kernels: Sequence[ColumnKernel]) -> List[str]:
    """Columns a run reads from the table (not produced inside the run),
    in first-use order."""
    ext: List[str] = []
    produced: set = set()
    for k in kernels:
        for c in k.input_cols:
            if c not in produced and c not in ext:
                ext.append(c)
        produced.update(k.output_cols)
    return ext


def _output_cols(kernels: Sequence[ColumnKernel]) -> List[str]:
    out: List[str] = []
    for k in kernels:
        for c in k.output_cols:
            if c not in out:
                out.append(c)
    return out


def _closure_outputs(kernels: Sequence[ColumnKernel],
                     requested: Sequence[str]) -> Tuple[str, ...]:
    """``requested`` plus the materialization pins its dependency closure
    demands: for every kernel with ``pin_inputs`` that the requested
    columns (transitively) depend on, the kernel's chain-produced input
    columns join the program outputs — materializing them pins the fusion
    boundary so the kernel's context-sensitive ops (transcendentals,
    matmuls) lower exactly as in the stand-alone per-stage program.
    Kernels outside the closure stay dead code."""
    producer = {}
    for j, k in enumerate(kernels):
        for c in k.output_cols:
            producer[c] = j
    needed: set = set()
    stack = [producer[c] for c in requested if c in producer]
    while stack:
        j = stack.pop()
        if j in needed:
            continue
        needed.add(j)
        stack.extend(
            producer[c] for c in kernels[j].input_cols if c in producer
        )
    pins: List[str] = []
    for j in sorted(needed):
        if kernels[j].pin_inputs:
            for c in kernels[j].input_cols:
                if c in producer and c not in pins:
                    pins.append(c)
    return tuple(dict.fromkeys([*pins, *requested]))


def _chain_fn(kernels: Sequence[ColumnKernel], ext_names: Sequence[str],
              out_names: Sequence[str], bucket: int, policy=None):
    """The pure cols→cols chain function for ``kernels``, returning only
    ``out_names``. Constants arrive as traced arguments (sorted by name
    per kernel) so model-data value changes reuse the compiled
    executable, and the row count arrives as a traced scalar (the
    validity mask is built on device, so differing row counts within a
    bucket share one program AND allocate nothing host-side). Columns NOT
    in ``out_names`` — and every kernel feeding only such columns — are
    dead code XLA eliminates, which is how lazy intermediate columns cost
    nothing until someone reads them.

    A mixed ``policy`` casts every float input and constant down to
    ``policy.compute`` at the program boundary (the sanctioned
    step-boundary down-cast the FML6xx walker recognizes) and builds the
    validity mask at ``policy.compute`` so the mask multiply doesn't
    silently promote the whole chain back to f32. A quantized policy
    (``policy.quant == "int8"``) receives eligible model constants as
    :class:`QuantizedConst` pairs and dequantizes them to
    ``policy.compute`` here — int8 in HBM/transfer, float in the math,
    never an integer accumulation (the FML606 contract)."""
    import jax
    import jax.numpy as jnp

    kernels = tuple(kernels)
    ext_names = tuple(ext_names)
    out_names = tuple(out_names)
    # A mixed policy (compute narrower than params) casts every float
    # boundary value to compute; the QUANTIZED tier does too (its
    # declared compute width is where the dequant-fused math runs —
    # under x64, f64 activations must come down to f32 or the tier
    # silently runs double-width). A plain FULL/None policy stays
    # inert (the PR 10 contract: no policy, no change).
    casts = policy is not None and (policy.mixed or policy.quant is not None)
    mask_dt = (
        jnp.dtype(policy.compute_dtype) if casts else jnp.float32
    )
    compute_dt = (
        jnp.dtype(policy.compute_dtype) if policy is not None
        else jnp.float32
    )

    def _to_compute(v):
        if casts and jnp.issubdtype(v.dtype, jnp.floating) \
                and v.dtype != mask_dt:
            return v.astype(mask_dt)
        return v

    def _const_to_compute(v):
        if isinstance(v, QuantizedConst):
            # Dequant at compute width, in-program: XLA fuses the
            # convert+mul into the consuming matmul/elementwise op.
            return v.q.astype(compute_dt) * v.scale.astype(compute_dt)
        return _to_compute(v)

    def run(ext_vals, const_vals, n_valid):
        # Kernels resolve active_policy() at TRACE time, and this body
        # runs at trace time — on whatever thread first calls the jitted
        # program. A lazy column's deferred trace (another thread, or
        # after the scope exited) would otherwise compile under the
        # READER's ambient policy while cached and validated under the
        # CAPTURED key, so the captured policy is pinned for the trace.
        prev = active_policy()
        _POLICY.value = policy
        try:
            valid = (jnp.arange(bucket) < n_valid).astype(mask_dt)
            ext_vals = tuple(_to_compute(v) for v in ext_vals)
            const_vals = tuple(
                tuple(_const_to_compute(v) for v in cv) for cv in const_vals
            )
            cols = dict(zip(ext_names, ext_vals))
            last = len(kernels) - 1
            for i, (kernel, cv) in enumerate(zip(kernels, const_vals)):
                consts = dict(zip(sorted(kernel.constants), cv))
                outs = kernel.fn(
                    {c: cols[c] for c in kernel.input_cols}, consts, valid
                )
                if i != last:
                    # Pin per-stage rounding: without the barrier XLA's
                    # algebraic simplifier rewrites across stage
                    # boundaries (e.g. two chained scaler divisions
                    # (x/s1)/s2 become x/(s1*s2)), breaking the
                    # bit-parity contract with the per-stage path. Still
                    # ONE program / one dispatch; only cross-stage op
                    # rewriting is fenced.
                    outs = jax.lax.optimization_barrier(outs)
                cols.update(outs)
            return {c: cols[c] for c in out_names}
        finally:
            _POLICY.value = prev

    return run


def _validate_chain(chain, ext_vals, const_vals, kernels, policy) -> None:
    """The fused executor's pre-compile FML6xx gate: trace ``chain``
    abstractly over the real (padded) buffers and check the jaxpr
    against the active policy. External columns are ``data``, model-data
    constants are ``param`` (an f16/bf16-STORED coefficient fails
    FML603), and any narrow accumulation or smuggled wide constant
    inside a kernel fails FML601/FML602 — all BEFORE jit sees the
    chain. Raises
    :class:`~flinkml_tpu.precision.PrecisionValidationError`."""
    import jax
    import numpy as _np

    from flinkml_tpu.analysis.precision import validate_precision

    validate_precision(
        chain, tuple(ext_vals), tuple(const_vals), _np.int32(1),
        policy=policy, param_argnums=(1,),
        program="pipeline_fusion["
                + "+".join(type(k).__name__ for k in kernels) + "]",
    )


def _build_chain(kernels, ext_names, out_names, bucket, policy):
    """:func:`_chain_fn`'s callable under the name a profile calls the
    program's runs, ``fused_chain``."""
    return named_program(
        "fused_chain", _chain_fn(kernels, ext_names, out_names, bucket, policy))


def _placement_ids(ext_vals) -> Tuple[int, ...]:
    """Device ids the chain's inputs sit on — the placement signature
    the AOT cache keys a loaded executable by (a compiled artifact is
    bound to one placement; ``jax.jit`` would silently recompile per
    placement, a ``Compiled`` must be retarget-loaded instead)."""
    import jax

    for v in ext_vals:
        devices = getattr(v, "devices", None)
        if callable(devices):
            try:
                ids = tuple(sorted(d.id for d in v.devices()))
            except Exception:  # noqa: BLE001 — fall through to default
                continue
            if ids:
                return ids
    # jax_default_device may be a Device, a platform-name STRING (e.g.
    # JAX_DEFAULT_DEVICE=cpu), or None — only a Device carries an id.
    default_id = getattr(jax.config.jax_default_device, "id", None)
    return (default_id if default_id is not None
            else jax.devices()[0].id,)


def _run_program(kernels, ext_names, out_names, ext_specs, const_specs,
                 ext_vals, const_vals, bucket: int, n: int, policy=None):
    """Compile-or-reuse the program for (chain, requested outputs,
    bucket, policy) and execute it; returns the dict of bucket-padded
    output buffers. ``policy`` is captured ONCE per
    :func:`execute_kernel_chain` and passed down explicitly, so a lazy
    column's deferred program — possibly materialized on another thread
    or after the scope exited — compiles under the SAME policy as its
    eager siblings.

    With an active :mod:`flinkml_tpu.compile_cache` store the program is
    AOT-compiled (``jit(...).lower(...).compile()``) through the store:
    a fresh process LOADS the serialized executable instead of paying
    the XLA compile, and one replica's compile serves every other
    replica via retargeted loads. Loaded programs are placement-bound,
    so the in-memory key grows the input placement signature; without a
    store the jit path (and its key) is exactly as before."""
    import jax

    from flinkml_tpu import compile_cache

    group = metrics.group("pipeline.fusion")
    store = compile_cache.active_store()
    # What decides the program, and nothing else: the in-memory cache's
    # key, what ``on_compile`` hooks receive and the AOT store's identity.
    key = (
        tuple(k.fingerprint for k in kernels),
        tuple(ext_specs),
        const_specs,
        tuple(out_names),
        bucket,
        policy,
    )
    devsig = _placement_ids(ext_vals) if store is not None else None
    cache_key = key if store is None else key + (devsig,)
    with _LOCK:
        program = _CACHE.get(cache_key)
    if program is None and policy is not None:
        # Refusal precedes compile AND caching: a failing chain leaves
        # no executable behind (re-entry revalidates — validation is an
        # abstract trace, compile-free and cheap next to a compile).
        # This also gates AOT *loads*: a cached artifact only executes
        # in a process whose policy gate admits the same chain.
        with jax.enable_x64(True):
            _validate_chain(
                _chain_fn(kernels, ext_names, out_names, bucket, policy),
                ext_vals, const_vals, kernels, policy,
            )
    compiled = False
    if program is None and store is not None:
        def _build():
            with jax.enable_x64(True):
                return jax.jit(
                    _build_chain(kernels, ext_names, out_names, bucket,
                                 policy)
                ).lower(tuple(ext_vals), const_vals, np.int32(n)).compile()

        program, outcome = store.get_or_compile(
            ("pipeline_fusion", key), _build, device_ids=devsig,
        )
        with _LOCK:
            program = _CACHE.setdefault(cache_key, program)
        compiled = outcome in ("compiled", "uncached")
        if not compiled:
            group.counter("aot_loads")
    elif program is None:
        with _LOCK:
            program = _CACHE.get(cache_key)
            if program is None:
                program = jax.jit(
                    _build_chain(kernels, ext_names, out_names, bucket,
                                 policy)
                )
                _CACHE[cache_key] = program
                compiled = True
    if compiled:
        group.counter("compiles")
        for hook in list(on_compile):
            hook(key)
    else:
        group.counter("cache_hits")
    with jax.enable_x64(True):
        return program(
            tuple(ext_vals), const_vals, np.int32(n)
        )


def execute_kernel_chain(table: Table, kernels: Sequence[ColumnKernel]) -> Table:
    """Run ``kernels`` over ``table`` as one fused program.

    One host→device upload per external host-resident input column, zero
    host transfers for device-resident inputs and intermediates, and a
    result table whose new columns are device-resident (host copy deferred
    to :meth:`Table.column`).
    """
    import jax
    import jax.numpy as jnp

    if not kernels:
        return table
    group = metrics.group("pipeline.fusion")
    n = table.num_rows
    bucket = row_bucket(n)
    ext = external_inputs(kernels)
    out_names = _output_cols(kernels)

    # Partition outputs: a column consumed by a later kernel of the run is
    # an *intermediate* — nobody may ever read it, so it is not computed
    # eagerly. The eager program returns only terminal columns, XLA
    # dead-code-eliminates the rest (on the CPU fallback this alone is the
    # difference between ~1x and ~3x over per-stage execution: four unread
    # [rows, dim] float64 buffers never get written). Intermediates become
    # LazyDeviceColumns: first access runs a DCE'd program for just that
    # column, through the same compile cache.
    producer = {}
    for j, k in enumerate(kernels):
        for c in k.output_cols:
            producer[c] = j
    terminal = [
        c for c in out_names
        if not any(
            c in kernels[j].input_cols
            for j in range(producer[c] + 1, len(kernels))
        )
    ]
    # Terminals plus the pinned inputs their closure demands (pin_inputs
    # kernels need their input columns materialized for bit parity).
    eager_names = list(_closure_outputs(kernels, terminal))
    lazy_names = [c for c in out_names if c not in eager_names]

    with jax.enable_x64(True):
        ext_vals = []
        ext_specs = []
        for name in ext:
            if not table.has_device_copy(name):
                # The upload below is a real host→device copy; further
                # transforms over this (immutable) table hit the cache.
                group.counter("host_to_device_transfers")
                group.counter(
                    "host_to_device_bytes", float(table.column(name).nbytes)
                )
            arr = table.device_column_padded(name, bucket)
            ext_vals.append(arr)
            ext_specs.append((name, str(arr.dtype), tuple(arr.shape[1:])))

        # The active policy is key material AND decides the constant
        # representation: under a quantized (int8) tier, eligible model
        # constants upload as per-column absmax int8 + f32 scales — the
        # bandwidth tier — and dequantize inside the program.
        policy = active_policy()
        quant_min = (
            _quant_min_elems()
            if policy is not None and policy.quant == "int8" else None
        )

        def _const_entry(name, raw):
            if quant_min is not None:
                from flinkml_tpu import precision as _precision

                host = np.asarray(raw)
                if _precision.quantizable(host, quant_min):
                    key = (id(host), host.shape, str(host.dtype),
                           quant_min, name)
                    with _QUANT_LOCK:
                        hit = _QUANT_CONST_CACHE.get(key)
                        if hit is not None and hit[0] is host:
                            _QUANT_CONST_CACHE.move_to_end(key)
                            return hit[1], hit[2]
                    q, s = _precision.quantize_absmax(host)
                    val = QuantizedConst(jnp.asarray(q), jnp.asarray(s))
                    # The spec names the QUANTIZED representation (plus
                    # the original shape): a genuinely-int8 constant can
                    # never alias a quantized-float one, and the autotune
                    # threshold changing which constants quantize re-keys
                    # the program through these specs.
                    spec = (name, "int8[absmax]", False,
                            tuple(host.shape))
                    with _QUANT_LOCK:
                        _QUANT_CONST_CACHE[key] = (host, val, spec)
                        _QUANT_CONST_CACHE.move_to_end(key)
                        while len(_QUANT_CONST_CACHE) > _QUANT_CONST_MAX:
                            _QUANT_CONST_CACHE.popitem(last=False)
                    return val, spec
            v = jnp.asarray(raw)
            return v, (name, str(v.dtype),
                       bool(getattr(v, "weak_type", False)),
                       tuple(v.shape))

        # weak_type is part of the spec: a python-scalar constant
        # (float64 weak) and an array constant (float64 strong) promote
        # DIFFERENTLY inside the program (weak * f32 -> f32, strong * f32
        # -> f64), so two chains differing only there must not alias one
        # cached executable.
        with span("fusion.constants"):
            const_pairs = tuple(
                tuple(_const_entry(c, k.constants[c])
                      for c in sorted(k.constants))
                for k in kernels
            )
        const_vals = tuple(tuple(v for v, _ in kc) for kc in const_pairs)
        const_specs = tuple(tuple(s for _, s in kc) for kc in const_pairs)

        # Abstract trace (no compile, no compute): padded shape/dtype of
        # every output, for lazy-column construction and the bytes-avoided
        # accounting. Cached alongside the programs. The active policy is
        # key material here too: a mixed program's outputs ARE narrower.
        spec_key = (
            tuple(k.fingerprint for k in kernels),
            tuple(ext_specs),
            const_specs,
            "__specs__",
            bucket,
            policy,
        )
        with _LOCK:
            specs = _CACHE.get(spec_key)
        if specs is None:
            abstract = jax.eval_shape(
                _chain_fn(kernels, ext, out_names, bucket, policy),
                tuple(ext_vals), const_vals, np.int32(n),
            )
            specs = {
                c: (tuple(v.shape), v.dtype) for c, v in abstract.items()
            }
            with _LOCK:
                _CACHE[spec_key] = specs

    with span("fusion.dispatch"):
        outs = _run_program(
            kernels, ext, eager_names, ext_specs, const_specs,
            ext_vals, const_vals, bucket, n, policy,
        )

    group.counter("fused_segments")
    group.counter("fused_stages", float(len(kernels)))
    # Per-stage execution would download every intermediate column and
    # re-upload it for the next stage; fused, those bytes never move.
    avoided = 0.0
    for name in lazy_names:
        shape, dtype = specs[name]
        row = int(np.prod(shape[1:], dtype=np.int64))
        avoided += 2.0 * n * row * np.dtype(dtype).itemsize
    if avoided:
        group.counter("host_transfer_bytes_avoided", avoided)

    # Outputs stay bucket-padded behind PaddedDeviceColumn: result
    # construction costs no device work; the prefix slice (and any
    # device→host copy) happens lazily at column access. Intermediates go
    # one step lazier: even their compute waits for the first read.
    result = table
    for name in eager_names:
        result = result.with_column(
            name, PaddedDeviceColumn(outs[name], n)
        )
    for name in lazy_names:
        shape, dtype = specs[name]

        def thunk(name=name, policy=policy):
            try:
                return _run_program(
                    kernels, ext, _closure_outputs(kernels, (name,)),
                    ext_specs, const_specs, ext_vals, const_vals, bucket, n,
                    policy,
                )[name]
            except RuntimeError as e:
                if "deleted" in str(e).lower() or "donat" in str(e).lower():
                    raise RuntimeError(
                        f"lazy intermediate column {name!r} cannot be "
                        "materialized: a source device buffer was donated "
                        "or freed before its first read. Read the column "
                        "(table.column(name)) before donating/deleting the "
                        "buffers the fused program captured."
                    ) from e
                raise

        result = result.with_column(
            name, LazyDeviceColumn(thunk, n, shape, dtype)
        )
    return result
