"""Operations and bytes that a step of the multilayer perceptron *needs*,
from its shapes (``flops_bytes.py`` is the dense kernels' and is not
edited; ``readers/roofline_of_program.py`` looks here). As there: what NO
implementation can avoid, so a share cannot pass 100 %.
"""

from __future__ import annotations


def step(rows: int, layers, itemsize: int = 4) -> dict:
    """One Adam step of a perceptron ``layers = [d_0, ..., d_L]`` over a
    batch of ``rows`` rows.

    Flops: a multiply and an add a weight a row in each product. A layer
    has three: forward ``h W``, the weights' gradient ``h^T d`` and the
    inputs' ``d W^T``; the FIRST layer has no inputs' gradient to make
    (nothing lies before the rows), so its ``2 rows d_0 d_1`` are not
    counted: ``6 rows sum_l d_{l-1} d_l - 2 rows d_0 d_1`` (1.112 TFLOP at
    784-2500-2000-1500-1000-500-10 and 16,384 rows; the customary ``6 N``
    a row would be 1.176). The activations' tanh, the softmax and Adam's
    dozen operations a parameter are under a thousandth of that and are
    left out.
    Bytes: the window's rows read once; every parameter and both its
    moments read and written once (Adam; the products read the parameters
    they update). Activations and gradients need not leave the chip.
    ~ 3,000 flop/byte: bound by flops at the bfloat16 peak on every chip
    of peaks.json."""
    layers = [int(d) for d in layers]
    weights = sum(a * b for a, b in zip(layers, layers[1:]))
    params = weights + sum(layers[1:])
    return {
        "flops": float(6 * rows * weights - 2 * rows * layers[0] * layers[1]),
        "bytes": float(rows * (layers[0] + 2) * itemsize + 6 * params * itemsize),
    }
