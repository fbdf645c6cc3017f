"""Operations and bytes that a level of histogram boosting *needs*, from
its shapes (``flops_bytes.py`` is the dense kernels' and is not edited;
``readers/roofline_of_program.py`` looks here). As there: what NO
implementation can avoid, so a share cannot pass 100 %.
"""

from __future__ import annotations


def level(rows: int, features: int, bins: int, depth: int,
          stat_itemsize: int = 4) -> dict:
    """The MEAN of a tree's ``depth`` levels over ``rows`` rows of
    ``features`` one-byte bins: the program's device time is read over
    all of them, per level.

    Bytes: every cell's bin read once (a byte); a row's ``g``, ``h`` and
    node read once and its node, re-assigned, written once; the level's
    histograms of ``g`` and ``h`` written once (``nodes x features x
    bins``, the mean of ``2^level`` nodes over the levels: kilobytes
    beside the table). The gradients' own pass (a row's label, weight
    and prediction read, ``g`` and ``h`` written, once a TREE) and the
    prediction's update are the program's, a twelfth of a level's bytes
    each, not counted.
    Flops: an addition a cell for each of ``g`` and ``h``. The one-hot
    products a chip makes those additions with (2 x 256 x 128 and more a
    cell) are how it does them, not what the algorithm needs.
    ~ 1 flop/byte: bound by bytes on every chip of peaks.json."""
    nodes = ((1 << depth) - 1) / depth
    return {
        "flops": float(2 * rows * features),
        "bytes": float(rows * features + rows * 4 * stat_itemsize
                       + 2 * nodes * features * bins * stat_itemsize),
    }
