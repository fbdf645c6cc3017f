"""Pallas fused transform chain — one kernel per row bucket.

The fused executor (:mod:`flinkml_tpu.pipeline_fusion`) compiles a run
of kernel-capable stages into ONE ``jax.jit`` program; under XLA the
per-bucket program is a fused jaxpr that XLA re-schedules per bucket.
This module lowers the same chain as ONE Pallas kernel instead: the
grid walks row tiles of the bucket, each ``[TILE, …]`` block of every
external input column stays VMEM-resident while the scaler/assembler/
encoder/model stages run back-to-back on it, the validity mask is built
in-kernel from the traced row count (``rows < n`` per tile — identical
values to the XLA chain's ``arange(bucket) < n``), and each output
column's tile is stored once at the end. Model constants ride as full
(untiled) blocks, so model-data refreshes reuse the compiled kernel
exactly like the XLA path.

Semantics are pinned to :func:`flinkml_tpu.pipeline_fusion._chain_fn`:

- same policy boundary — a mixed :class:`PrecisionPolicy` casts float
  externals/constants to ``policy.compute`` BEFORE the kernel and
  builds the mask at ``policy.compute``;
- same trace-time policy pinning (kernel fns resolve
  ``active_policy()`` while tracing — inside the Pallas body that trace
  happens under the captured policy, never the reader thread's);
- row-local ops are bit-identical under the interpreter (elementwise
  and per-row reductions do not see the tiling); the f32 matmul
  carve-out documented on the executor applies to compiled TPU runs.

No ``optimization_barrier`` between stages: stages run inside one
Mosaic kernel where XLA's cross-stage algebraic rewriting (the thing
the barrier fences) never happens, and the interpreter evaluates the
ops stage-by-stage anyway.

Supported shapes/dtypes (the refusal surface — see
``docs/development/kernels.md``): >= 1 kernel; every external input,
constant, and output row-leading or constant-shaped with dtype kind in
f/i/u/b; no weak-typed (python-scalar) constants — Pallas refs are
strong-typed and would change jnp promotion; float64 only under the
interpreter; bucket divisible by the row tile (always true — buckets
are powers of two >= 8).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

#: Row-tile ceiling: buckets up to here run as one tile (shape-identical
#: to the XLA program); larger buckets tile at 1024 rows — the block a
#: rank-1 float32 output column (``prediction [bucket]``) must have on
#: the TPU, where XLA lays rank-1 arrays out in tiles of 1024 (a 128-row
#: tile is refused: "XLA layout T(1024) does not match Mosaic layout
#: T(128)").
MAX_ROW_TILE = 1024


def row_tile(bucket: int) -> int:
    return bucket if bucket <= MAX_ROW_TILE else MAX_ROW_TILE


def _sorted_consts(kernel) -> Tuple[str, ...]:
    return tuple(sorted(kernel.constants))


def _apply_chain(kernels, ext_names, out_names, ext_arrays, const_arrays,
                 valid):
    """The chain math, shared by the eval-shape probe and the kernel
    body — the SAME per-kernel call protocol as ``_chain_fn`` (consts
    sorted by name; each kernel sees exactly its input columns)."""
    cols = dict(zip(ext_names, ext_arrays))
    for kernel, cv in zip(kernels, const_arrays):
        consts = dict(zip(_sorted_consts(kernel), cv))
        outs = kernel.fn(
            {c: cols[c] for c in kernel.input_cols}, consts, valid
        )
        cols.update(outs)
    return tuple(cols[c] for c in out_names)


def _eval_out_struct(kernels, ext_names, out_names, bucket, policy,
                     ext_vals, const_vals, mask_dt):
    """Abstract output specs of the (policy-cast) chain, traced under
    the captured policy exactly as the real program will be."""
    import jax
    import jax.numpy as jnp

    from flinkml_tpu import pipeline_fusion as pf

    prev = pf.active_policy()
    pf._POLICY.value = policy
    try:
        return jax.eval_shape(
            lambda e, c: _apply_chain(
                kernels, ext_names, out_names, e, c,
                jnp.zeros((bucket,), mask_dt),
            ),
            tuple(ext_vals), tuple(const_vals),
        )
    finally:
        pf._POLICY.value = prev


def _mask_dtype(policy):
    import jax.numpy as jnp

    mixed = policy is not None and policy.mixed
    return jnp.dtype(policy.compute_dtype) if mixed else jnp.float32


def _cast_boundary(policy, ext_vals, const_vals):
    """The sanctioned program-boundary down-cast — identical to
    ``_chain_fn``'s ``_to_compute`` over externals and constants."""
    import jax.numpy as jnp

    mixed = policy is not None and policy.mixed
    if not mixed:
        return tuple(ext_vals), tuple(tuple(cv) for cv in const_vals)
    dt = jnp.dtype(policy.compute_dtype)

    def to_compute(v):
        if jnp.issubdtype(v.dtype, jnp.floating):
            return v.astype(dt)
        return v

    return (
        tuple(to_compute(v) for v in ext_vals),
        tuple(tuple(to_compute(v) for v in cv) for cv in const_vals),
    )


def unsupported_reason(kernels, ext_names: Sequence[str],
                       out_names: Sequence[str], bucket: int, policy,
                       ext_vals, const_vals,
                       interpret: bool) -> Optional[str]:
    """Why the Pallas chain cannot run this program (None = it can).
    Checked only when the gate resolves to ``pallas`` — the default-off
    path never pays the abstract trace."""
    import jax.numpy as jnp

    if not kernels:
        return "empty chain"
    for kernel, cv in zip(kernels, const_vals):
        for name, v in zip(_sorted_consts(kernel), cv):
            if getattr(v, "weak_type", False):
                return (
                    f"constant {name!r} of {type(kernel).__name__} is "
                    "weak-typed (python-scalar model datum) — Pallas "
                    "refs are strong-typed and would change promotion"
                )
            if not interpret and v.dtype == jnp.float64:
                return (f"constant {name!r} is float64 — "
                        "interpreter-only (TPU has no f64 lanes)")
    for name, v in zip(ext_names, ext_vals):
        if v.dtype.kind not in "fiub":
            return f"input column {name!r} has dtype {v.dtype}"
        if not interpret and v.dtype == jnp.float64:
            return (f"input column {name!r} is float64 — "
                    "interpreter-only (TPU has no f64 lanes)")
    mask_dt = _mask_dtype(policy)
    ext_c, const_c = _cast_boundary(policy, ext_vals, const_vals)
    try:
        out_struct = _eval_out_struct(
            kernels, tuple(ext_names), tuple(out_names), bucket, policy,
            ext_c, const_c, mask_dt,
        )
    except Exception as e:  # noqa: BLE001 — the reason IS the refusal
        return f"chain does not abstract-trace: {type(e).__name__}: {e}"
    for name, s in zip(out_names, out_struct):
        if s.ndim == 0 or s.shape[0] != bucket:
            return (f"output {name!r} is not row-leading "
                    f"(shape {s.shape}, bucket {bucket}) — cross-row "
                    "kernels have no Pallas chain path")
        if not interpret and s.dtype == jnp.float64:
            return (f"output {name!r} is float64 — interpreter-only "
                    "(TPU has no f64 lanes)")
    return None


def pallas_chain_fn(kernels, ext_names: Sequence[str],
                    out_names: Sequence[str], bucket: int, policy=None):
    """Drop-in replacement for ``pipeline_fusion._chain_fn`` — the same
    ``run(ext_vals, const_vals, n_valid) -> {col: array}`` contract,
    lowered through one row-tiled ``pallas_call`` per program."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from flinkml_tpu import pipeline_fusion as pf
    from flinkml_tpu.kernels import _gate

    kernels = tuple(kernels)
    ext_names = tuple(ext_names)
    out_names = tuple(out_names)
    mask_dt = _mask_dtype(policy)
    tile = row_tile(bucket)
    interpret = _gate.interpret_mode()

    def run(ext_vals, const_vals, n_valid):
        # Pin the captured policy for the whole trace (same rationale as
        # _chain_fn: kernel fns resolve active_policy() at trace time,
        # and a lazy column may trace on another thread).
        prev = pf.active_policy()
        pf._POLICY.value = policy
        try:
            ext_c, const_c = _cast_boundary(policy, ext_vals, const_vals)
            out_struct = _eval_out_struct(
                kernels, ext_names, out_names, bucket, policy,
                ext_c, const_c, mask_dt,
            )
            for name, s in zip(out_names, out_struct):
                if s.ndim == 0 or s.shape[0] != bucket:
                    raise _gate.KernelUnsupportedError(
                        f"kernels[fused_chain]: output {name!r} is not "
                        f"row-leading (shape {s.shape}, bucket {bucket})"
                    )

            # Flatten constants; 0-d scalars ride as (1,) blocks and are
            # restored inside the body (Pallas blocks are >= 1-d).
            flat_consts, was_scalar, split = [], [], []
            for cv in const_c:
                split.append(len(cv))
                for v in cv:
                    was_scalar.append(v.ndim == 0)
                    flat_consts.append(v.reshape(1) if v.ndim == 0 else v)
            n_ext, n_const = len(ext_c), len(flat_consts)

            def body(n_ref, *refs):
                ext_refs = refs[:n_ext]
                const_refs = refs[n_ext:n_ext + n_const]
                out_refs = refs[n_ext + n_const:]
                i = pl.program_id(0)
                rows = jax.lax.broadcasted_iota(
                    jnp.int32, (tile, 1), 0
                )[:, 0] + i * tile
                valid = (rows < n_ref[0]).astype(mask_dt)
                ext_arrays = tuple(r[...] for r in ext_refs)
                flat = [
                    r[...][0] if scalar else r[...]
                    for r, scalar in zip(const_refs, was_scalar)
                ]
                const_arrays, pos = [], 0
                for count in split:
                    const_arrays.append(tuple(flat[pos:pos + count]))
                    pos += count
                outs = _apply_chain(
                    kernels, ext_names, out_names, ext_arrays,
                    tuple(const_arrays), valid,
                )
                for o_ref, o in zip(out_refs, outs):
                    o_ref[...] = o

            def tiled(shape):
                trailing = tuple(shape[1:])
                zeros = (0,) * len(trailing)
                return pl.BlockSpec(
                    (tile,) + trailing, lambda i, _z=zeros: (i,) + _z
                )

            def full(shape):
                zeros = (0,) * len(shape)
                return pl.BlockSpec(
                    tuple(shape), lambda i, _z=zeros: _z
                )

            outs = pl.pallas_call(
                body,
                grid=(bucket // tile,),
                in_specs=(
                    [full((1,))]
                    + [tiled(v.shape) for v in ext_c]
                    + [full(v.shape) for v in flat_consts]
                ),
                out_specs=tuple(tiled(s.shape) for s in out_struct),
                out_shape=tuple(
                    _gate.out_struct(s.shape, s.dtype, *ext_c,
                                     *flat_consts)
                    for s in out_struct
                ),
                interpret=interpret,
            )(
                jnp.asarray(n_valid, jnp.int32).reshape(1),
                *ext_c, *flat_consts,
            )
            return dict(zip(out_names, outs))
        finally:
            pf._POLICY.value = prev

    return run
