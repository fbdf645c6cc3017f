"""Pallas level histograms — a tree level's (node, feature, bin) sums of
gradients and hessians as one-hot products in fast memory.

A level of histogram boosting sums ``g`` and ``h`` over the rows of every
(node, feature, bin). As a scatter that is ``rows x features`` keyed
additions (``segment_sum``: 7 ns a cell on a v5e, PR 28); as a product it
is, for a tile of rows, ``A = one_hot(node) (x) (g, h)`` against ``B_f =
one_hot(bin of feature f)``: ``H_f += B_f A^T``, the features sharing
``A``. Through XLA the one-hots go to HBM and come back (``[tile, 256]``
a feature: 765 GB a level at 115 M rows); here a tile of rows meets the
features in turn and a feature's one-hot is made, multiplied and dropped
in VMEM.

Everything lies with the ROWS along the lanes, as the chip holds the
table: ``bins [features, rows]`` uint8, ``g``, ``h`` and ``node`` a row of
lanes each. A tile's ``A`` is ``[columns, tile]``: for each of the three
bfloat16 parts of ``g`` and of ``h`` (:func:`~flinkml_tpu.kernels._split.
rounded_parts`; the products with a 0/1 operand are then exact and the
MXU sums in float32) a group of ``max(nodes, 8)`` sublanes, the part
where the row's node is that sublane and 0 elsewhere, the whole padded
with zeros to full MXU columns (:func:`columns`). A feature's ``B`` is
``[256, tile]``, the bin against an iota down the sublanes. The product
contracts the tile (the lanes of both); its ``[256, columns]`` float32
is added to the feature's sums of the current run of tiles, and a
run's sums (:data:`RUN_TILES` tiles) to the level's, both in VMEM over
the grid's one axis. That axis is sequential: one fixed order, no
atomics, the same bits every run. The parts' sums are added as they lie outside
the kernel (:func:`level_histograms`).

Traced in 32-bit mode whatever the caller's (PR 30).
"""

from __future__ import annotations

import functools
from typing import Optional

from flinkml_tpu.kernels._split import rounded_parts
from flinkml_tpu.kernels.sparse_blocks import _as_operand, _one_hot

LANES = 128
#: Sublanes of a float32 vreg: a part's group of node rows is whole vregs.
SUBLANES = 8
#: Bins a feature's one-hot holds (``maxBins`` at most).
BINS = 256
#: Rows a grid step holds (the most; :func:`tile_rows`).
TILE = 4096
#: Tiles whose sums are added apart before they join the total.
RUN_TILES = 128
#: Fast memory the kernel may use (a v5e has 128 MiB, the compiler's own
#: limit is 16): :func:`vmem_bytes` has what it holds.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
#: bfloat16 parts of ``g`` and of ``h`` (:func:`~flinkml_tpu.kernels.
#: _split.rounded_parts`).
PARTS = 3


def columns(nodes: int) -> int:
    """Columns of a tile's ``A``: the parts of ``g`` and of ``h``, each a
    group of ``max(nodes, 8)`` rows, up to whole MXU tiles."""
    used = 2 * PARTS * max(nodes, SUBLANES)
    return -(-used // LANES) * LANES


def vmem_bytes(features: int, nodes: int, tile: int) -> int:
    """Fast memory a level of ``nodes`` nodes over ``features`` features
    holds: the level's sums in the output's two buffers and a run's beside
    them, ``A`` and a feature's one-hot at float32 and at bfloat16, the
    tile's bins twice at a byte and once at 32 bits. (13 features: 10 MB
    of sums at ``maxDepth`` 8's last level, three times; 100 features
    pass the limit at its sixth.)"""
    width = columns(nodes)
    return (3 * features * BINS * width * 4 + tile * (BINS + width) * 6
            + features * tile * 6)


def tile_rows(rows: int) -> Optional[int]:
    """Rows a grid step: the most, of :data:`TILE` halved down to 128,
    that divide ``rows``; None where none does."""
    tile = TILE
    while tile >= LANES:
        if rows and rows % tile == 0:
            return tile
        tile //= 2
    return None


def unsupported_reason(stat_dtype, bin_dtype, features: int, rows: int,
                       nodes: int, max_bins: int = BINS) -> Optional[str]:
    """Why the kernel does not take this level of ``nodes`` nodes over
    ``[features, rows]`` bins a device (None = it does)."""
    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        return f"backend {jax.default_backend()}: a Mosaic kernel"
    if jnp.dtype(stat_dtype) != jnp.float32:
        return f"gradients {stat_dtype}: the parts are a float32's"
    if jnp.dtype(bin_dtype) != jnp.uint8:
        return f"bins {bin_dtype}: the table is one byte a cell"
    if max_bins > BINS:
        return f"{max_bins} bins: a one-hot holds {BINS}"
    tile = tile_rows(rows)
    if tile is None:
        return f"{rows} rows a device are not whole tiles of {LANES}"
    if vmem_bytes(features, nodes, tile) > VMEM_LIMIT_BYTES:
        return (f"{nodes} nodes a level of {features} features: their sums "
                "would not stay in fast memory")
    return None


def _body(bins_ref, g_ref, h_ref, node_ref, out_ref, wide_ref, run_ref, *,
          nodes: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)
        run_ref[...] = jnp.zeros(run_ref.shape, jnp.float32)

    features, tile = bins_ref.shape
    group = max(nodes, SUBLANES)
    of_node = _one_hot(node_ref[...], group)
    pieces = []
    for stat_ref in (g_ref, h_ref):
        for part in rounded_parts(stat_ref[...], in_kernel=True):
            pieces.append(jnp.where(of_node, jnp.broadcast_to(
                part.astype(jnp.float32), (group, tile)), 0.0))
    rest = out_ref.shape[2] - len(pieces) * group
    if rest:
        pieces.append(jnp.zeros((rest, tile), jnp.float32))
    a = jnp.concatenate(pieces, axis=0).astype(jnp.bfloat16)
    # The bins at 32 bits, once a tile: a feature's row is then a
    # dynamic sublane of a 32-bit array.
    wide_ref[...] = bins_ref[...].astype(jnp.int32)

    def one_feature(f, carry):
        of_bin = _as_operand(_one_hot(wide_ref[pl.ds(f, 1), :], BINS))
        run_ref[f] += jax.lax.dot_general(
            of_bin, a, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, features, one_feature, 0)

    # A run of tiles is summed apart and added to the total as one term:
    # 28,160 tiles' sums added one after another carry 1e-5 of float32's
    # rounding into a 115-million-term sum, 220 runs of 128 a sixth of it.
    @pl.when((step % RUN_TILES == RUN_TILES - 1) | (step == pl.num_programs(0) - 1))
    def _():
        out_ref[...] += run_ref[...]
        run_ref[...] = jnp.zeros(run_ref.shape, jnp.float32)


def level_sums(bins, g, h, node, nodes: int, *,
               tile: Optional[int] = None, interpret: Optional[bool] = None):
    """``[features, 256, columns(nodes)]`` float32: for feature
    ``f`` and bin ``b``, column ``(s * PARTS + p) * max(nodes, 8) + w``
    holds the sum over the rows of node ``w`` whose bin of ``f`` is ``b``
    of part ``p`` of ``g`` (``s`` 0) or ``h`` (``s`` 1). ``bins
    [features, rows]`` uint8, ``g``, ``h`` ``[rows]`` float32, ``node
    [rows]`` int32 in ``[0, nodes)``; ``rows`` whole tiles
    (:func:`tile_rows`)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from flinkml_tpu.kernels import _gate

    if interpret is None:
        interpret = _gate.interpret_mode()
    features, rows = bins.shape
    tile = tile or tile_rows(rows)
    width = columns(nodes)
    row_of_lanes = pl.BlockSpec((1, tile), lambda t: (0, t))
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_body, nodes=nodes),
            grid=(rows // tile,),
            in_specs=[pl.BlockSpec((features, tile), lambda t: (0, t)),
                      row_of_lanes, row_of_lanes, row_of_lanes],
            out_specs=pl.BlockSpec((features, BINS, width), lambda t: (0, 0, 0)),
            out_shape=_gate.out_struct((features, BINS, width), jnp.float32,
                                       bins, g, h, node),
            scratch_shapes=[pltpu.VMEM((features, tile), jnp.int32),
                            pltpu.VMEM((features, BINS, width), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(bins, g[None, :], h[None, :], node[None, :])


def level_histograms(bins, g, h, node, nodes: int, *,
                     interpret: Optional[bool] = None):
    """``(hg, hh)``, each ``[nodes, features, 256]`` float32: the sums of
    ``g`` and of ``h`` over the rows of every (node, feature, bin); the
    operands :func:`level_sums`'. The parts' sums added as they lie."""
    sums = level_sums(bins, g, h, node, nodes, interpret=interpret)
    group = max(nodes, SUBLANES)
    out = []
    for s in range(2):
        total = None
        for p in range(PARTS):
            at = (s * PARTS + p) * group
            part = sums[:, :, at:at + nodes]
            total = part if total is None else total + part
        out.append(total.transpose(2, 0, 1))
    return out[0], out[1]
