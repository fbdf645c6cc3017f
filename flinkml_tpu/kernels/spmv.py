"""Pallas padded-ELL CSR SpMV — the sparse forward-margin matvec.

The sparse trainers' forward pass is one ELL matvec per step:
``dot[r] = sum_s values[r, s] * w[indices[r, s]]`` over a padded
``[rows, width]`` block (the ELL convention: index 0 / value 0 cells
contribute exactly 0). XLA lowers ``w[indices]`` as one gather that
materializes the whole ``[rows, width]`` gathered matrix before the
reduce; this kernel tiles the rows (grid over ``rows / ROW_TILE``) so
the gather target is one ``[ROW_TILE, width]`` VMEM-resident block and
the multiply-reduce never leaves VMEM. Per row the op tree — gather,
elementwise multiply, ``sum`` over the width axis — is identical to the
XLA reference ``jnp.sum(values * w[indices], axis=1)``, so results are
bit-identical to the JITTED reference at every dtype (the product path
is always jitted; an eager reference can differ in the last f32 bit
because XLA's unfused reduce uses a different association tree).

**Interpreter-only.** The kernel does not compile on a TPU (checked
against Mosaic for a v5e, jax 0.9.0): its body is
``jnp.take(w_ref[...], idx_ref[...])``, an arbitrary-index gather from
a ``[dim]`` vector, and Mosaic lowers only a same-shape 2-D
``take_along_axis`` (``NotImplementedError: Only 2D gather is
supported``); its rank-1 output blocks of ``ROW_TILE`` rows are refused
too (rank-1 blocks must be multiples of 128). A compiled SpMV needs a
different design (per-row DMA gathers or a one-hot matmul), so
``unsupported_reason(..., interpret=False)`` says so and an explicit
``FLINKML_TPU_KERNELS=pallas`` on the chip raises
:class:`KernelUnsupportedError` at this site instead of failing inside
the compiler. The gate (:mod:`flinkml_tpu.kernels._gate`, site
``spmv``) keeps XLA the default.
"""

from __future__ import annotations

from typing import Optional

#: Row tile (grid unit). 8 = f32 sublane count; rows pad up to a
#: multiple with zero rows that are sliced off after the call.
ROW_TILE = 8

#: Why there is no compiled path (module docstring); lands verbatim in
#: :class:`KernelUnsupportedError`.
NO_TPU_LOWERING = (
    "the kernel does not compile on TPU: its body gathers w[indices] "
    "from a [dim] vector with arbitrary indices, and Mosaic lowers only "
    "a same-shape 2-D take_along_axis ('Only 2D gather is supported'); "
    "it runs under the interpreter only"
)


def unsupported_reason(indices, values, w, interpret: bool) -> Optional[str]:
    """Why the Pallas kernel cannot run these operands (None = it can).
    The wording lands verbatim in :class:`KernelUnsupportedError`."""
    import jax.numpy as jnp

    if indices.ndim != 2 or values.ndim != 2:
        return (f"indices/values must be [rows, width], got ranks "
                f"{indices.ndim}/{values.ndim}")
    if tuple(indices.shape) != tuple(values.shape):
        return (f"indices shape {tuple(indices.shape)} != values shape "
                f"{tuple(values.shape)}")
    if w.ndim != 1:
        return f"w must be [dim], got rank {w.ndim}"
    if not jnp.issubdtype(indices.dtype, jnp.integer):
        return f"indices dtype {indices.dtype} is not integer"
    if not jnp.issubdtype(values.dtype, jnp.floating):
        return (f"values dtype {values.dtype} is not floating (supported: "
                "bfloat16/float32, + float64 under the interpreter)")
    if values.dtype != w.dtype:
        return f"values dtype {values.dtype} != w dtype {w.dtype}"
    if not interpret:
        return NO_TPU_LOWERING
    return None


def _spmv_body(idx_ref, val_ref, w_ref, out_ref):
    import jax.numpy as jnp

    gathered = jnp.take(w_ref[...], idx_ref[...], axis=0)
    out_ref[...] = jnp.sum(val_ref[...] * gathered, axis=1)


def pallas_spmv(indices, values, w, *, interpret: Optional[bool] = None):
    """``sum(values * w[indices], axis=1)`` over a padded ELL block —
    bit-compatible with the XLA reference at every dtype. Unsupported
    operands raise :class:`KernelUnsupportedError` (same typed refusal
    as the gated dispatcher)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from flinkml_tpu.kernels import _gate

    if interpret is None:
        interpret = _gate.interpret_mode()
    reason = unsupported_reason(indices, values, w, interpret)
    if reason is not None:
        raise _gate.KernelUnsupportedError(
            f"kernels[spmv]: pallas_spmv cannot run these operands: "
            f"{reason}"
        )
    rows, width = values.shape
    idx32 = indices.astype(jnp.int32)
    pad = (-rows) % ROW_TILE
    if pad:
        idx32 = jnp.concatenate([idx32, jnp.zeros((pad, width), jnp.int32)])
        values = jnp.concatenate(
            [values, jnp.zeros((pad, width), values.dtype)]
        )
    grid = (idx32.shape[0] // ROW_TILE,)
    out = pl.pallas_call(
        _spmv_body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROW_TILE, width), lambda i: (i, 0)),
            pl.BlockSpec((ROW_TILE, width), lambda i: (i, 0)),
            pl.BlockSpec((w.shape[0],), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((ROW_TILE,), lambda i: (i,)),
        out_shape=_gate.out_struct(
            (idx32.shape[0],), values.dtype, idx32, values, w),
        interpret=interpret,
    )(idx32, values, w)
    return out[:rows] if pad else out


def spmv(indices, values, w, *, backend: Optional[str] = None):
    """The gated dispatcher: ``jnp.sum(values * w[indices], axis=1)``
    under ``"xla"``, :func:`pallas_spmv` under ``"pallas"``.
    ``backend=None`` resolves the gate (env > autotune table > xla); a
    passed backend is an explicit request and refuses unsupported
    operands loudly. Zero-row and zero-width blocks always take the XLA
    path (nothing to tile)."""
    import jax.numpy as jnp

    from flinkml_tpu.kernels import _gate

    indices = jnp.asarray(indices)
    values = jnp.asarray(values)
    w = jnp.asarray(w)
    if values.ndim == 2 and 0 in values.shape:
        return jnp.sum(values * jnp.take(w, indices, axis=0), axis=1)
    interpret = _gate.interpret_mode()
    chosen = _gate.resolve_checked(
        "spmv", unsupported_reason(indices, values, w, interpret), backend,
    )
    if chosen == "pallas":
        return pallas_spmv(indices, values, w, interpret=interpret)
    return jnp.sum(values * jnp.take(w, indices, axis=0), axis=1)


def factory_backend() -> str:
    """The resolved spmv backend for callers that bake it into a jit
    static argument (the lru-key idiom — see the gate module)."""
    from flinkml_tpu.kernels import _gate

    return _gate.backend_for("spmv")
