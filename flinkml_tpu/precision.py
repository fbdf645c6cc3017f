"""PrecisionPolicy — the declared mixed-precision contract (ROADMAP 2).

The paper's premise — replacing JVM BLAS inner loops with XLA kernels —
only pays off on TPU when compute runs in bf16 *without* silently
corrupting f32 accumulators or parameters. SNIPPETS.md [2]'s
``TPU_DTYPE = bfloat16`` / ``DTYPE = float32`` split and [3]'s
``to_bf16``/``to_fp32`` param casting under pjit are the exemplar
patterns; this module hardens them from a convention into a *checked*
policy value:

- ``compute`` — the dtype the hot elementwise/matmul work runs in (the
  bandwidth/MXU savings dtype, typically ``bfloat16``);
- ``accum`` — the minimum dtype any reduction/accumulation (``reduce_sum``,
  a dot-general accumulator, an optimizer moment update, a cross-rank
  psum) may run in (typically ``float32``);
- ``params`` — the dtype parameters and optimizer state are *stored* in
  between steps (typically ``float32``; cast down to ``compute`` at step
  boundaries, exactly the [3] idiom).

A policy is frozen, hashable (it keys compile caches — bf16 and f32
programs must never alias one executable) and JSON round-trippable (it
rides ``*.policy.json`` analysis fixtures). Every policy-gated entry
point — the fused transform executor (:mod:`flinkml_tpu.pipeline_fusion`),
the plan-sharded SGD/Adam trainers (:mod:`flinkml_tpu.sharding.apply`),
and serving inference (:class:`~flinkml_tpu.serving.engine.ServingConfig`
``.precision``) — validates its jaxpr against the policy BEFORE any
compile via the FML6xx precision-flow pass
(:mod:`flinkml_tpu.analysis.precision`), raising the typed
:class:`PrecisionValidationError` carrying the findings — the same
contract shape as ``PlanValidationError`` for FML5xx.

See ``docs/development/precision.md`` for the casting contract and the
equivalence-test recipe.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Optional

import numpy as np

#: Canonical float dtype names a policy may declare.
_FLOAT_NAMES = ("bfloat16", "float16", "float32", "float64")

#: Rounding-significand widths (bits) — the *precision* order, which is
#: what accumulation correctness cares about. Plain itemsize would rank
#: bfloat16 (8-bit significand) equal to float16 (11-bit); both are
#: "narrow" against float32, but the distinction keeps messages honest.
_SIGNIFICAND_BITS = {"bfloat16": 8, "float16": 11, "float32": 24,
                     "float64": 53}


def float_name(dtype) -> str:
    """Canonical name of a float dtype (accepts names, np dtypes, jnp
    scalar types, ml_dtypes)."""
    if isinstance(dtype, str) and dtype in _FLOAT_NAMES:
        return dtype
    name = np.dtype(dtype).name if not isinstance(dtype, str) else dtype
    if name not in _FLOAT_NAMES:
        raise ValueError(
            f"{dtype!r} is not a float dtype a PrecisionPolicy can "
            f"declare (one of {_FLOAT_NAMES})"
        )
    return name


def significand_bits(dtype) -> int:
    """Significand width of a float dtype name/np dtype (non-floats
    return a sentinel wider than every float — integer/bool values never
    count as 'narrow')."""
    try:
        name = float_name(dtype)
    except ValueError:
        return 1 << 16
    return _SIGNIFICAND_BITS[name]


def is_narrower(a, b) -> bool:
    """Whether float dtype ``a`` rounds coarser than ``b``."""
    return significand_bits(a) < significand_bits(b)


class PrecisionValidationError(ValueError):
    """A program failed FML6xx precision-flow validation against its
    declared :class:`PrecisionPolicy` — raised BEFORE any compile,
    carrying the rendered findings (rule ids + fix hints). The
    ahead-of-time half of the precision contract: a program that reaches
    jit has already passed the same checks
    ``python -m flinkml_tpu.analysis`` runs on ``*.policy.json``
    fixtures."""

    def __init__(self, message: str, findings=()):
        super().__init__(message)
        #: The structured :class:`~flinkml_tpu.analysis.findings.Finding`
        #: list behind the rendered message (CI annotates from these).
        self.findings = list(findings)


#: Quantization schemes a policy may declare for model constants.
_QUANT_SCHEMES = ("int8",)


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """The declared (compute, accum, params) dtype contract — see module
    docstring. Frozen + hashable (compile-cache key material), JSON
    round-trippable (``*.policy.json`` fixtures).

    ``quant`` declares a post-training-quantization scheme for model
    constants below the float tiers: ``"int8"`` stores/transfers every
    eligible model constant as per-column absmax-scaled int8
    (:func:`quantize_absmax`) and dequantizes to ``compute`` width
    INSIDE the fused program, so the dequant fuses into the consuming
    matmul/elementwise op. Accumulation still runs at ``accum`` — raw
    int8 accumulation (which wraps at ±127) is refused by FML606, and
    serving int8-stored params under a quant-less policy is refused by
    FML607 (the degraded values must never republish as the full-width
    tier)."""

    name: str = "custom"
    compute: str = "float32"
    accum: str = "float32"
    params: str = "float32"
    quant: Optional[str] = None

    def __post_init__(self):
        object.__setattr__(self, "compute", float_name(self.compute))
        object.__setattr__(self, "accum", float_name(self.accum))
        object.__setattr__(self, "params", float_name(self.params))
        if not self.quant:  # "" and None both mean "no quantization"
            object.__setattr__(self, "quant", None)
        elif self.quant not in _QUANT_SCHEMES:
            raise ValueError(
                f"policy {self.name!r}: unknown quantization scheme "
                f"{self.quant!r} (one of {_QUANT_SCHEMES}, or None)"
            )
        if is_narrower(self.accum, self.compute):
            raise ValueError(
                f"policy {self.name!r}: accum ({self.accum}) narrower than "
                f"compute ({self.compute}) — accumulating below the compute "
                "width is never intentional"
            )

    # -- dtype accessors (jax imported lazily: the policy value must be
    # -- constructible in host-only config code) ---------------------------
    @property
    def compute_dtype(self):
        return _np_dtype(self.compute)

    @property
    def accum_dtype(self):
        return _np_dtype(self.accum)

    @property
    def params_dtype(self):
        return _np_dtype(self.params)

    @property
    def mixed(self) -> bool:
        """Whether the policy narrows compute below params (i.e. whether
        the gate changes any program at all)."""
        return is_narrower(self.compute, self.params)

    def describe(self) -> str:
        return (f"{self.name}(compute={self.compute}, accum={self.accum}, "
                f"params={self.params})")

    # -- serialization -----------------------------------------------------
    def to_json_dict(self) -> dict:
        out = {"name": self.name, "compute": self.compute,
               "accum": self.accum, "params": self.params}
        if self.quant is not None:
            out["quant"] = self.quant
        return out

    @staticmethod
    def from_json_dict(d: Mapping) -> "PrecisionPolicy":
        quant = d.get("quant")
        return PrecisionPolicy(
            name=str(d.get("name", "custom")),
            compute=str(d.get("compute", "float32")),
            accum=str(d.get("accum", "float32")),
            params=str(d.get("params", "float32")),
            quant=None if quant in (None, "") else str(quant),
        )


def _np_dtype(name: str):
    """np.dtype for a canonical float name (bfloat16 via ml_dtypes,
    which every jax install ships)."""
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


# -- presets -----------------------------------------------------------------

#: No mixed precision: everything at float32. Exists mostly as the
#: explicit "other side" of A/B comparisons; ``None`` (no policy) leaves
#: programs untouched.
FULL = PrecisionPolicy("full", "float32", "float32", "float32")

#: The training policy (SNIPPETS.md [3]): bf16 compute, f32 accumulation
#: AND f32-stored parameters/optimizer state, cast down at step
#: boundaries. This is the policy the plan-sharded SGD/Adam trainers
#: implement and validate against.
MIXED = PrecisionPolicy("mixed", "bfloat16", "float32", "float32")

#: The inference policy: bf16 compute with bf16 per-op accumulation
#: (model data stays f32-stored). Inference carries no cross-step
#: accumulator state, and on TPU the MXU accumulates bf16 matmuls in
#: f32 in hardware, so per-op bf16 accumulation is the standard serving
#: trade; declare :data:`MIXED` instead to REFUSE any bf16-accumulating
#: kernel at load time (the strict gate).
MIXED_INFERENCE = PrecisionPolicy(
    "mixed_inference", "bfloat16", "bfloat16", "float32"
)

#: The post-training-quantized serving tier BELOW ``mixed_inference``:
#: eligible model constants are stored and transferred as per-column
#: absmax-scaled int8 (+ one float32 scale per column) and dequantized
#: to float32 inside the fused program, where XLA fuses the dequant into
#: the consuming matmul — compute and accumulation stay at float32, so
#: nothing integer ever accumulates (FML606 refuses exactly that shape).
#: On CPU meshes this tier also beats bf16 ``mixed_inference`` rows/s
#: outright: bf16 is software-emulated there while the dequantized
#: program runs native f32. Not measured on the chip.
INT8_INFERENCE = PrecisionPolicy(
    "int8_inference", "float32", "float32", "float32", quant="int8"
)

PRESET_POLICIES = {
    p.name: p for p in (FULL, MIXED, MIXED_INFERENCE, INT8_INFERENCE)
}


def resolve_policy(policy) -> Optional[PrecisionPolicy]:
    """Accept a policy object, a preset name, a JSON dict, or None."""
    if policy is None or isinstance(policy, PrecisionPolicy):
        return policy
    if isinstance(policy, str):
        try:
            return PRESET_POLICIES[policy]
        except KeyError:
            raise ValueError(
                f"unknown precision preset {policy!r} (presets: "
                f"{sorted(PRESET_POLICIES)})"
            ) from None
    if isinstance(policy, Mapping):
        return PrecisionPolicy.from_json_dict(policy)
    raise TypeError(f"cannot interpret {policy!r} as a PrecisionPolicy")


# -- post-training quantization (the int8 tier's storage transform) ----------

#: Constants smaller than this many elements are left at float width by
#: the int8 tier: per-column scales plus dequant overhead outweigh the
#: bandwidth saved on tiny vectors. Overridable per mesh via the
#: ``int8_min_const_elems`` autotune knob (consulted by the fused
#: executor at key-construction time — the resolved set of quantized
#: constants is cache-key material through the constant specs).
INT8_MIN_CONST_ELEMS = 16


def quantizable(arr, min_elems: int = INT8_MIN_CONST_ELEMS) -> bool:
    """Whether the int8 tier quantizes this model constant: a float
    array with at least ``min_elems`` elements. Integer/bool constants
    (lookup sizes, category counts) and tiny vectors pass through at
    their storage width."""
    a = np.asarray(arr)
    try:
        float_name(a.dtype)
    except ValueError:
        return False
    return a.size >= int(min_elems) and a.ndim >= 1


def quantize_absmax(arr):
    """Per-column absmax int8 quantization of one model constant.

    For a rank-``n >= 2`` array the scale is per LAST-axis column
    (absmax over every leading axis — the per-output-column scheme for a
    ``[in, out]`` matmul weight); a 1-D vector gets one per-tensor
    scale. Returns ``(q, scale)`` with ``q`` int8 in ``[-127, 127]`` and
    ``scale`` float32 such that ``q * scale ≈ arr``; an all-zero column
    gets scale 1.0 (quantizes to zeros exactly). Symmetric around zero —
    ``-128`` is never produced, so negation round-trips."""
    a = np.asarray(arr)
    if a.ndim >= 2:
        absmax = np.max(np.abs(a), axis=tuple(range(a.ndim - 1)))
    else:
        absmax = np.max(np.abs(a)) if a.size else np.float64(0.0)
    scale = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
    q = np.clip(
        np.rint(a / scale.astype(a.dtype)), -127, 127
    ).astype(np.int8)
    return q, scale


def dequantize_absmax(q, scale, dtype="float32"):
    """The inverse transform at ``dtype`` width (host-side reference;
    the fused executor performs the same two ops in-program so XLA fuses
    them into the consumer)."""
    dt = np.dtype(dtype)
    return np.asarray(q).astype(dt) * np.asarray(scale).astype(dt)


def cast_floats(tree, dtype):
    """Cast every float leaf of a pytree to ``dtype`` (the
    ``to_bf16``/``to_fp32`` idiom); non-float leaves pass through."""
    import jax

    dt = np.dtype(dtype)

    def one(leaf):
        leaf_dt = np.asarray(leaf).dtype if not hasattr(leaf, "dtype") \
            else leaf.dtype
        if np.dtype(leaf_dt) == dt or significand_bits(leaf_dt) >= (1 << 16):
            return leaf
        return leaf.astype(dt) if hasattr(leaf, "astype") else \
            np.asarray(leaf, dt)

    return jax.tree_util.tree_map(one, tree)
