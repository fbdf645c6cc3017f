"""``kernels/_split``: a float32 as three bfloat16 parts, in the two forms
the Mosaic kernels use (rounded, and disjoint bit fields), held to the
input bit for bit where that holds and to what is lost where it does not
(parts under 2^-126 flush to zero; the sum of -0's parts is +0; a rounded
``hi`` of float32's largest values is bfloat16's infinity)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flinkml_tpu.kernels._split import disjoint_parts, rounded_parts

SMALLEST_NORMAL = 2.0 ** -126
BF16_MAX = np.float32(3.3895314e38)
F32_MAX = np.finfo(np.float32).max

FORMS = {
    # As XLA runs it around a kernel: one fusion, roundings by
    # reduce_precision.
    "rounded": jax.jit(lambda v: rounded_parts(v, in_kernel=False)),
    # As Mosaic runs it: every cast kept (here op by op, no fusion).
    "rounded_in_kernel": lambda v: rounded_parts(v, in_kernel=True),
    "disjoint": jax.jit(disjoint_parts),
}


def _values(kind):
    rng = np.random.default_rng(0)
    if kind == "normals":
        v = rng.integers(0, 2 ** 32, 200_000, dtype=np.uint64) \
            .astype(np.uint32).view(np.float32)
        keep = np.isfinite(v) & (np.abs(v) >= 2.0 ** -100) \
            & (np.abs(v) <= BF16_MAX)
        return np.concatenate([
            np.float32([1.0, -1.0, 1 + 2 ** -8 + 2 ** -23, 2.0 ** -100,
                        BF16_MAX, -BF16_MAX]), v[keep]])
    if kind == "zeros":
        return np.float32([0.0, -0.0])
    if kind == "tiny":
        sub = rng.integers(1, 1 << 23, 1_000).astype(np.uint32)
        small = ((rng.integers(1, 26, 1_000).astype(np.uint32) << 23)
                 | rng.integers(0, 1 << 23, 1_000).astype(np.uint32))
        v = np.concatenate([sub, small]).view(np.float32)
        return np.concatenate([v, -v, np.float32([1e-45, 1.1754942e-38,
                                                  SMALLEST_NORMAL])])
    assert kind == "largest"
    return np.float32([F32_MAX, -F32_MAX])


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize("kind", ["normals", "zeros", "tiny", "largest"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_parts_of_a_float32_sum_to_it(form, kind):
    v = _values(kind)
    assert v.dtype == np.float32
    parts = [np.asarray(p) for p in FORMS[form](jnp.asarray(v))]
    hi, mid, lo = (p.astype(np.float32) for p in parts)
    for p in (hi, mid, lo):   # each part is a bfloat16, exactly
        np.testing.assert_array_equal(
            _bits(jnp.asarray(p).astype(jnp.bfloat16).astype(jnp.float32)),
            _bits(p))
    with np.errstate(invalid="ignore", over="ignore"):
        as_they_lie = (hi + mid) + lo
        other_orders = [(lo + hi) + mid, hi + (mid + lo)]
    if kind == "largest" and form != "disjoint":
        # A rounded hi of float32's largest values is bfloat16's
        # infinity: this form is for operands under 3.39e38.
        assert not np.isfinite(hi).any()
        return
    if kind == "normals":
        np.testing.assert_array_equal(_bits(as_they_lie), _bits(v))
        if form != "disjoint":
            # The data holds values whose ``hi + lo`` is no float32 (a
            # 25th bit): what makes the order of the sum matter.
            exact = hi.astype(np.float64) + lo.astype(np.float64)
            assert np.any((hi + lo).astype(np.float64) != exact)
    elif kind == "largest":
        np.testing.assert_array_equal(_bits(as_they_lie), _bits(v))
    elif kind == "zeros":
        np.testing.assert_array_equal(as_they_lie, v)       # -0 == +0
        assert all(not p.any() for p in (hi, mid, lo))
    else:
        # Only what a flush to zero takes is lost.
        lost = np.abs(as_they_lie.astype(np.float64) - v.astype(np.float64))
        assert lost.max() < SMALLEST_NORMAL
    if form == "disjoint" and kind in ("normals", "largest"):
        # Disjoint bit fields: exact in any order.
        for s in other_orders:
            np.testing.assert_array_equal(_bits(s), _bits(v))
