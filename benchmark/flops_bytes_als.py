"""Operations and bytes that a half-step of ALS *needs*, from its shapes
(``flops_bytes.py`` is the dense kernels' and is not edited;
``readers/roofline_of_program.py`` looks here). As there: what NO
implementation can avoid, so a share cannot pass 100 %.
"""

from __future__ import annotations


def half_step(ratings: int, users: int, items: int, rank: int,
              itemsize: int = 4, index_itemsize: int = 4) -> dict:
    """The MEAN of an iteration's two half-steps (the users' from the
    item factors, the items' from the users'): the program's device time
    is read over both, per half-step.

    Flops, a half-step over ``targets`` of one side: a multiply and an
    add per rating for each entry of the symmetric ``y y'`` that has to
    be formed, ``ratings * k * (k + 1)`` (the lower triangle; the other
    half is its mirror); the right-hand side ``sum r y``, ``2 * ratings *
    k``; a Cholesky factorisation and two triangular solves a target,
    ``targets * (k**3 / 3 + 2 * k**2)``. Counted ONCE, as float32
    arithmetic, against ``peaks.json``'s bfloat16 rate (``flops_bytes_
    knn``'s convention): the six bfloat16 passes of a float32 product
    are how the chip does it, not what the algorithm needs.
    Bytes: every rating's index and value read once; both factor tables
    once (the fixed side read, the solved side written). The fixed-side
    row a rating names is NOT counted once a rating (``ratings * k *
    itemsize``, 101 GB at the cell's size): a blocked half-step can hold
    rows in fast memory across the targets that share them, so that is
    the program's own traffic, reported in PERF.md, not the algorithm's
    need.
    ~ 1,000 flop/byte: bound by flops on every chip of peaks.json."""
    targets = (users + items) / 2.0
    k = rank
    return {
        "flops": float(ratings * k * (k + 1) + 2.0 * ratings * k
                       + targets * (k ** 3 / 3.0 + 2.0 * k ** 2)),
        "bytes": float(ratings * (index_itemsize + itemsize)
                       + (users + items) * k * itemsize),
    }
