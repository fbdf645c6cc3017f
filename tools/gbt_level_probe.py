"""Device readings behind ``kernels/gbt_hist``'s fold: what a level of
``gbt-airline.fit`` pays for its histograms, the kernel alone at the
cell's own shapes (115,343,360 x 13 one-byte bins drawn on the device,
``g`` and ``h`` as a first tree's, a node a row), shape by shape.

Prints one JSON line a reading, seconds a call (the host's clock around
``reps`` calls, each waited for; the least):

- ``rule``: :func:`~flinkml_tpu.kernels.gbt_hist.level_sums` as the rule
  (``gbt_hist.fold``) shapes it, at every node count asked for;
- ``unfolded``: the same level with the rule patched to "never" (the
  product ``[256, tile] x [columns, tile]`` of before the fold), and
  whether the two levels' sums are equal to the bit on the chip;
- ``no_product``: both, with the product left out: the operands are
  built as they are and OR-ed down to one lane tile in place of the
  MXU's passes (a vector operation a packed vreg that the kernel does
  not have: 512 a feature and tile folded, 768 unfolded), so an UPPER
  bound of the kernel's vector work;
- ``unrolled``: the rule's level with the loop over the features
  unrolled (``fori_loop(..., unroll=True)``, which the kernel does not
  do: ROADMAP B4 (ii)): the same passes and the same vector work with
  one feature's start and end laid over its neighbours', so what the
  level is above this is the loop's turn and what this is above the
  passes' time at the MXU's peak is everything else.

Run it through the chip tool: ``python tools/gbt_level_probe.py [seed
[nodes,nodes,...]]`` (1, 8, 16, 32 by default; about five minutes).
"""

import functools
import json
import os
import sys
import time
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS, FEATURES, REPS = 115_343_360, 13, 3


def _or_of_lanes(x):
    """``[n, tile]`` bfloat16 as ``[n / 2, 128]`` int32: its lane tiles
    OR-ed together (every vreg read, one operation each)."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    words = pltpu.bitcast(x, jnp.int32)
    return functools.reduce(
        lambda a, b: a | b,
        [words[:, at:at + 128] for at in range(0, words.shape[1], 128)])


def _no_product(lhs, rhs, dimension_numbers, preferred_element_type):
    """In ``dot_general``'s place: ``[lhs rows, rhs rows]`` of no meaning
    that depends on every vreg of both operands. (Operands stored to a
    scratch and a corner read back cost nothing and keep nothing: the
    compiler drops the stores and what built them, 0.027 s a level.)"""
    import jax.numpy as jnp

    rows = _or_of_lanes(lhs)
    cols = _or_of_lanes(rhs)
    cols = functools.reduce(
        lambda a, b: a | b, [cols[at:at + 8] for at in range(0, cols.shape[0], 8)])
    tile = jnp.concatenate([rows, rows], axis=0) | jnp.broadcast_to(
        cols[:1], (lhs.shape[0], 128))
    return jnp.concatenate([tile] * (rhs.shape[0] // 128), axis=1).astype(
        preferred_element_type)


def main(seed: int, levels):
    import jax
    import jax.numpy as jnp

    from flinkml_tpu.kernels import gbt_hist

    if jax.default_backend() != "tpu":
        raise SystemExit(f"backend {jax.default_backend()}: the readings are a chip's")
    kb, kg, kn = jax.random.split(jax.random.PRNGKey(seed % (1 << 31)), 3)
    bins = jax.random.randint(kb, (FEATURES, ROWS), 0, 256, jnp.int32).astype(jnp.uint8)
    y = (jax.random.uniform(kg, (ROWS,)) < 0.2).astype(jnp.float32)
    g, h = 0.2 - y, jnp.full((ROWS,), 0.16, jnp.float32)
    jax.block_until_ready((bins, g, h))
    lines = []

    def emit(**line):
        lines.append(line)
        print(json.dumps(line), flush=True)

    def reading(nodes, node, *, folds, product=True, unroll=False):
        patches = [] if folds is None else [mock.patch.object(gbt_hist, "fold", lambda n: folds)]
        if not product:
            patches.append(mock.patch.object(jax.lax, "dot_general", _no_product))
        if unroll:
            patches.append(mock.patch.object(
                jax.lax, "fori_loop", functools.partial(jax.lax.fori_loop, unroll=True)))
        for patch in patches:
            patch.start()
        try:
            run = jax.jit(lambda b, g, h, n: gbt_hist.level_sums(
                b, g, h, n, nodes, interpret=False))
            sums = jax.block_until_ready(run(bins, g, h, node))      # compiles
            took = []
            for _ in range(REPS):
                start = time.perf_counter()
                jax.block_until_ready(run(bins, g, h, node))
                took.append(time.perf_counter() - start)
            return min(took), gbt_hist.unfolded(sums, nodes)
        finally:
            for patch in patches:
                patch.stop()

    for nodes in levels:
        node = jax.random.randint(kn, (ROWS,), 0, nodes, jnp.int32)
        shape = {"nodes": nodes, "fold": gbt_hist.fold(nodes),
                 "one_hot_rows": gbt_hist.one_hot_rows(nodes),
                 "columns": gbt_hist.columns(nodes)}
        rule_s, rule_sums = reading(nodes, node, folds=None)
        emit(reading="rule", seconds=rule_s, **shape)
        if shape["fold"]:
            plain_s, plain_sums = reading(nodes, node, folds=False)
            emit(reading="unfolded", nodes=nodes, seconds=plain_s,
                 cells_off=int(jnp.sum(rule_sums != plain_sums)),
                 cells=int(rule_sums.size))
            emit(reading="no_product", nodes=nodes, fold=False,
                 seconds=reading(nodes, node, folds=False, product=False)[0])
        emit(reading="no_product", nodes=nodes, fold=shape["fold"],
             seconds=reading(nodes, node, folds=None, product=False)[0])
        unrolled_s, unrolled_sums = reading(nodes, node, folds=None, unroll=True)
        emit(reading="unrolled", nodes=nodes, seconds=unrolled_s,
             cells_off=int(jnp.sum(rule_sums != unrolled_sums)))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gbt_level_probe.jsonl", "w") as out:
        out.writelines(json.dumps(line) + "\n" for line in lines)


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 0,
         [int(n) for n in sys.argv[2].split(",")] if len(sys.argv) > 2 else [1, 8, 16, 32])
