"""``kernels/_split``: a float32 as three bfloat16 parts, in the two forms
the Mosaic kernels use (rounded, and disjoint bit fields), held to the
input bit for bit where that holds and to what is lost where it does not
(parts under 2^-126 flush to zero; the sum of -0's parts is +0; a rounded
``hi`` of float32's largest values is bfloat16's infinity); and as four
int8 digits of its bits, the form a lookup's product can carry, which
loses nothing whatever the bits are."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flinkml_tpu.kernels._split import (
    digits, disjoint_parts, joined_digits, rounded_parts)

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


#: What the parts lose and the digits must not: the zeros' signs, the
#: infinities, a NaN's payload (quiet and signalling), subnormals, the
#: least and greatest finite floats.
ODD_BITS = np.asarray(
    [0x00000000, 0x80000000, 0x7F800000, 0xFF800000, 0x7FC00001, 0xFFC12345,
     0x7F800001, 0x00000001, 0x80000001, 0x007FFFFF, 0x00800000, 0x7F7FFFFF,
     0xFF7FFFFF, 0x3F800001, 0xFFFFFFFF, 0x7FFFFFFF, 0x80808080, 0x7F7F7F7F],
    np.uint32)


@pytest.mark.parametrize("kind", ["odd", "any", "normals", "tiny", "largest"])
@pytest.mark.parametrize("jitted", [False, True], ids=["op-by-op", "jitted"])
def test_the_digits_of_a_float32_are_its_bits(kind, jitted):
    """Four int8 digits in ``[-128, 128)`` whose ``d0 + 256 (d1 + 256 (d2
    + 256 d3))`` is the float's 32 bits in two's complement: nothing of
    the float is computed with, so nothing of it is lost."""
    if kind == "odd":
        v = ODD_BITS.view(np.float32)
    elif kind == "any":
        v = np.random.default_rng(1).integers(
            0, 2 ** 32, 200_000, dtype=np.uint64).astype(np.uint32).view(np.float32)
    else:
        v = _values(kind)
    four = (jax.jit(digits) if jitted else digits)(jnp.asarray(v))
    assert len(four) == 4
    assert all(d.dtype == jnp.int8 and d.shape == v.shape for d in four)
    d0, d1, d2, d3 = (np.asarray(d).astype(np.int64) for d in four)
    for d in (d0, d1, d2, d3):
        assert d.min() >= -128 and d.max() < 128
    # in two's complement: the sum wraps where the top digit carried
    bits = (d0 + 256 * (d1 + 256 * (d2 + 256 * d3))) % 2 ** 32
    assert bits.astype(np.uint32).tobytes() == v.tobytes()
    # as a kernel puts them together: shifts and adds in int32
    joined = np.asarray(joined_digits([d.astype(jnp.int32) for d in four]))
    assert joined.dtype == np.int32 and joined.tobytes() == v.tobytes()
