"""Operations and bytes that each timed kernel *needs*, from its shapes.
Each function states its count; a reader divides the larger of
``flops / peak flops`` and ``bytes / peak bytes`` by the measured kernel
time to get the roofline share. Counting what the algorithm needs (not
what the program happens to move) keeps a share from passing 100%.
"""

from __future__ import annotations


def dense_lr_step(batch: int, dim: int, itemsize: int = 4) -> dict:
    """One mini-batch step of dense binomial LR.

    Flops: forward ``X_b @ c`` (2*B*d) and gradient ``X_b^T @ m``
    (2*B*d); the O(B) margin terms are left out.
    Bytes: the batch's features read ONCE (a row's multiplier needs only
    that row's own dot product, so one pass can do both products:
    B*d*itemsize), labels and weights (2*B*itemsize), coefficient read,
    gradient written, coefficient written (3*d*itemsize).
    0.5 flop/byte at most: bound by bytes on every chip in peaks.json."""
    return {
        "flops": 4.0 * batch * dim,
        "bytes": float(batch * dim * itemsize + 2 * batch * itemsize
                       + 3 * dim * itemsize),
    }


def chain(rows: int, dim: int, itemsize: int = 4) -> dict:
    """One call of the fused five-stage chain over ``rows`` rows.

    Flops: four scalers at 2 per element, the dot product at 2 per
    element: 10*rows*dim (the sigmoid's O(rows) is left out).
    Bytes: features in (rows*dim*itemsize), outputs out: prediction
    (rows*itemsize) and rawPrediction (2*rows*itemsize). No
    intermediate column needs to leave the chip's registers."""
    return {
        "flops": 10.0 * rows * dim,
        "bytes": float(rows * dim * itemsize + 3 * rows * itemsize),
    }


def least_seconds(count: dict, peaks: dict, flops_key: str = "bf16_flops_per_s"):
    """``(seconds, bound)``: the least time the chip could take and which
    peak sets it (``"bytes"`` or ``"flops"``)."""
    by_flops = count["flops"] / peaks[flops_key]
    by_bytes = count["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_flops else (by_flops, "flops")
