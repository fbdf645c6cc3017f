"""A float32 as three bfloat16 parts, the two forms the kernels use, and
as four int8 digits of its bits, the form a lookup can use.

The MXU multiplies bfloat16. A float32 product at full precision is made
from parts: three times 8 bits of mantissa hold float32's 24, so
``v = hi + mid + lo`` with every part exact in bfloat16, each bfloat16
product is exact in float32, and the products summed in float32 are the
float32 product (``knn_search`` sums six of the nine, ``sparse_blocks``
and ``dense_step`` multiply the parts by one-hot rows or by another
split operand). Two ways of making the parts exist, and they differ for
reasons read off the chip (PERF.md section 6, PRs 35, 39, 40):

- :func:`rounded_parts` rounds: ``hi`` is ``v`` at bfloat16, ``mid`` what
  is left of it at bfloat16, ``lo`` what is left then. ``hi + mid + lo``
  is ``v`` bit for bit **when added as they lie** (``(hi + mid) + lo``,
  the order of a contraction over them); a partial sum that skips a part
  is not always a float32 (``hi + lo`` needs a 25th bit for 0.4 % of
  floats), so a caller that adds parts itself keeps that order. OUTSIDE
  a kernel the rounding has to be ``lax.reduce_precision``: inside one
  fusion XLA keeps a bfloat16 value it has just made at float32 ("excess
  precision"), ``rest - float32(bfloat16(rest))`` came out 0 on a v5e
  and ``lo`` with it, distances 1e-4 off, and no CPU test shows it.
  Mosaic lowers the casts alone and keeps them, so INSIDE a kernel the
  parts are plain casts.
- :func:`disjoint_parts` cuts: ``v``'s top sixteen bits, the top sixteen
  of what is left, and the rest. The parts are disjoint bit fields of
  one significand, float32s that are each exact in bfloat16, so a sum of
  any of them in any order is exact too, and there is nothing for a
  compiler's excess precision to keep: integer masks and exact
  subtractions, the same inside a kernel and outside. A cut part never
  rounds up, so this form also takes float32's largest values, where a
  rounded ``hi`` is bfloat16's infinity.

Where "bit for bit" ends, for both: a part under 2^-126 is flushed to
zero (the chip and XLA:CPU alike), so values under 2^-100 come back to
within 2^-126 and not exactly, and the sum of the parts of -0 is +0.

- :func:`digits` does no arithmetic on the float at all. A lookup
  SELECTS: its product's other operand is 0/1 and names a column once,
  so the float's 32 bits can travel as integers, four int8 digits a
  float, on the MXU at int8's rate (twice bfloat16's), and what the
  products pick, put together by shifts and adds, is the float's bits
  whatever they are: -0, an infinity, a NaN's payload, a subnormal. A
  SUM of floats cannot travel so (``kernels.payload_blocks``' long
  lookup since PR 53, ``kernels.sparse_blocks``' wide one since PR 58;
  both accumulations keep their three parts).

``tests/test_kernels_split.py`` holds all of it.
"""

from __future__ import annotations


def rounded_parts(v, *, in_kernel: bool):
    """``(hi, mid, lo)``, bfloat16, of a float32 ``v``: ``hi`` is ``v``
    rounded, ``mid`` what is left of it rounded, ``lo`` what is left then;
    in float32 ``(hi + mid) + lo`` is ``v`` again, bit for bit. Outside a
    kernel the roundings are ``lax.reduce_precision`` (module docstring)."""
    import jax
    import jax.numpy as jnp

    def rounded(a):
        """``a`` at bfloat16's precision, as bfloat16 and as float32."""
        if in_kernel:
            low = a.astype(jnp.bfloat16)
            return low, low.astype(jnp.float32)
        a = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        return a.astype(jnp.bfloat16), a

    hi, hi_of = rounded(v)
    mid, mid_of = rounded(v - hi_of)
    return hi, mid, ((v - hi_of) - mid_of).astype(jnp.bfloat16)


def disjoint_parts(v):
    """Three float32s whose sum is the float32 ``v`` bit for bit, each
    exact in bfloat16: ``v``'s top sixteen bits (sign, exponent, seven
    of mantissa), the top sixteen of what is left, and the rest; a sum
    of any of them in any order is exact (module docstring)."""
    import jax
    import jax.numpy as jnp

    def top(a):
        bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
        return jax.lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFF0000), jnp.float32)

    hi = top(v)
    rest = v - hi
    mid = top(rest)
    return hi, mid, rest - mid


def digits(floats):
    """A float32's 32 bits as four int8 digits, ``bits = d0 + 256 (d1 +
    256 (d2 + 256 d3))`` in two's complement, each ``d`` in ``[-128,
    128)``: the product of each with a 0/1 operand is exact on the MXU at
    int8's rate, twice bfloat16's, and the sum above of what the products
    pick is the float's bits again, whatever they are."""
    import jax
    import jax.numpy as jnp

    rest = jax.lax.bitcast_convert_type(floats, jnp.int32)
    out = []
    for _ in range(4):
        digit = ((rest + 128) & 255) - 128
        out.append(digit.astype(jnp.int8))
        rest = (rest - digit) >> 8
    return out


def joined_digits(four):
    """:func:`digits`' inverse on four int32 planes, ``four[0]`` to
    ``four[3]`` (what products of the digit planes with a 0/1 operand
    picked; a list, or a kernel's ref read plane by plane): the floats'
    bits, by three shifts and adds that wrap as two's complement does."""
    return (four[0] + (four[1] << 8)) + ((four[2] << 16) + (four[3] << 24))
