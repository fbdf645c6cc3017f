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
lanes each. A tile's ``A`` is ``[used, tile]``: for each of the three
bfloat16 parts of ``g`` and of ``h`` (:func:`~flinkml_tpu.kernels._split.
rounded_parts`; the products with a 0/1 operand are then exact and the
MXU sums in float32) a group of ``max(nodes, 8)`` sublanes, the part
where the row's node is that sublane and 0 elsewhere
(:func:`used_columns`). A feature's ``B`` is the bin against an iota
down the sublanes. The product contracts the tile (the lanes of both);
its float32 is added to the feature's sums of the current run of tiles,
and a run's sums (:data:`RUN_TILES` tiles) to the level's, both in VMEM
over the grid's one axis. That axis is sequential: one fixed order, no
atomics, the same bits every run. The parts' sums are added as they lie
outside the kernel (:func:`level_histograms`).

*The product's shape is chosen from the level's node count* (PR 48). The
MXU works in tiles of 128 columns: ``B [256, tile]`` against ``A``
padded to whole tiles costs ``256 x ceil(used / 128)`` passes a feature
and 128 rows, and at 1 to 8 nodes 48 of the 128 columns are in use.
With the bin written ``b = 2 m + r``, cell ``(b, c)`` of a feature's
sums is cell ``(m, r * used + c)`` of ``one_hot(m) [128, tile]`` against
``(A where r is 0 ; A where r is 1) [2 used -> whole tiles, tile]``: the
same products added in the same order, in other cells of the MXU's
output. That is ``128 x ceil(2 used / 128)`` passes, and a level is
*folded* where that is fewer (:func:`fold`): half at 1 to 8 nodes (96 of
128 columns in use), three tiles for four at 32; 16 nodes and 64 and
more cost the same either way and keep ``B [256, tile]``. The folded
columns are a feature's own, made inside the loop over the features:
``A`` packed two bfloat16 rows a 32-bit word (both rows of one lane),
AND-ed with the lane's mask of ``r`` and of ``not r``, one vector
operation a packed vreg. :func:`level_sums` returns the layout it
computed and :func:`unfolded` puts a folded one back (kilobytes, outside
the kernel). No knob chooses: the rule is a function of ``nodes``.

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


def used_columns(nodes: int) -> int:
    """Rows of a tile's ``A`` that are not padding: the parts of ``g`` and
    of ``h``, each a group of ``max(nodes, 8)`` rows."""
    return 2 * PARTS * max(nodes, SUBLANES)


def _mxu_tiles(n: int) -> int:
    return -(-n // LANES)


def fold(nodes: int) -> bool:
    """Whether a level of ``nodes`` nodes folds the bin's low bit into the
    product's columns: where a one-hot of 128 rows against twice the used
    columns is fewer MXU passes than one of 256 against them once. At 1
    to 8 nodes (48 used columns: 96 of one tile's 128 where 48 were) half
    the passes, at 32 (192: three tiles for two of a one-hot twice as
    tall) three quarters; at 16 and from 64 on the same, and not
    folded."""
    used = used_columns(nodes)
    return (BINS // 2) * _mxu_tiles(2 * used) < BINS * _mxu_tiles(used)


def one_hot_rows(nodes: int) -> int:
    """Rows of a feature's one-hot: the bin's, or the bin's without its
    low bit where the level folds it (:func:`fold`)."""
    return BINS // 2 if fold(nodes) else BINS


def columns(nodes: int) -> int:
    """Columns of the product a feature: ``A``'s used rows (twice, one
    copy a value of the bin's low bit, where the level folds it:
    :func:`fold`), up to whole MXU tiles."""
    used = used_columns(nodes)
    return _mxu_tiles(2 * used if fold(nodes) else used) * LANES


def vmem_bytes(features: int, nodes: int, tile: int) -> int:
    """Fast memory a level of ``nodes`` nodes over ``features`` features
    holds: the level's sums in the output's two buffers and a run's beside
    them, ``A`` and a feature's one-hot at float32 and at bfloat16 (a
    folded level's columns are bfloat16 alone and twice its ``A``: no
    more), the tile's bins twice at a byte and once at 32 bits. (13 features: 10 MB of sums at ``maxDepth`` 8's last level,
    three times; 100 features pass the limit at its sixth.)"""
    hot, width = one_hot_rows(nodes), columns(nodes)
    return (3 * features * hot * width * 4 + tile * (hot + width) * 6
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
          nodes: int, folded: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

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
    used, width = len(pieces) * group, out_ref.shape[2]
    if folded:
        # Two bfloat16 rows a 32-bit word, both of one lane: a row kept or
        # zeroed by its lane's bit is the word AND that lane's mask.
        a = pltpu.bitcast(jnp.concatenate(pieces, axis=0).astype(jnp.bfloat16),
                          jnp.int32)
        rest = [jnp.zeros(((width - 2 * used) // 2, tile), jnp.int32)] * (width > 2 * used)
    else:
        if width > used:
            pieces.append(jnp.zeros((width - used, tile), jnp.float32))
        a = jnp.concatenate(pieces, axis=0).astype(jnp.bfloat16)
    # The bins at 32 bits, once a tile: a feature's row is then a
    # dynamic sublane of a 32-bit array.
    wide_ref[...] = bins_ref[...].astype(jnp.int32)

    def one_feature(f, carry):
        of_row = wide_ref[pl.ds(f, 1), :]
        if folded:
            # b = 2 m + r: the one-hot is m's and half as tall, the columns
            # are A's where r is 0 and, beside them, A's where r is 1. The
            # masks (0 or all ones) are made on the one row of lanes.
            r = of_row & 1
            even, odd = (jnp.broadcast_to(mask, a.shape) for mask in (r - 1, -r))
            columns_of = pltpu.bitcast(
                jnp.concatenate([a & even, a & odd] + rest, axis=0), jnp.bfloat16)
            of_bin = _as_operand(_one_hot(of_row >> 1, BINS // 2))
        else:
            columns_of = a
            of_bin = _as_operand(_one_hot(of_row, BINS))
        run_ref[f] += jax.lax.dot_general(
            of_bin, columns_of, (((1,), (1,)), ((), ())),
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
    """``[features, one_hot_rows(nodes), columns(nodes)]`` float32. With
    ``c = (s * PARTS + p) * max(nodes, 8) + w`` the column of node ``w``'s
    part ``p`` of ``g`` (``s`` 0) or ``h`` (``s`` 1): where the level is
    not folded (:func:`fold`), ``[f, b, c]`` is the sum of that part over
    the node's rows whose bin of feature ``f`` is ``b``; where it is, that
    sum lies at ``[f, b >> 1, (b & 1) * used_columns(nodes) + c]``
    (:func:`unfolded` puts it back). ``bins [features, rows]`` uint8,
    ``g``, ``h`` ``[rows]`` float32, ``node [rows]`` int32 in ``[0,
    nodes)``; ``rows`` whole tiles (:func:`tile_rows`)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from flinkml_tpu.kernels import _mosaic

    if interpret is None:
        interpret = _mosaic.interpret_mode()
    features, rows = bins.shape
    tile = tile or tile_rows(rows)
    sums = (features, one_hot_rows(nodes), columns(nodes))
    row_of_lanes = pl.BlockSpec((1, tile), lambda t: (0, t))
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_body, nodes=nodes, folded=fold(nodes)),
            grid=(rows // tile,),
            in_specs=[pl.BlockSpec((features, tile), lambda t: (0, t)),
                      row_of_lanes, row_of_lanes, row_of_lanes],
            out_specs=pl.BlockSpec(sums, lambda t: (0, 0, 0)),
            out_shape=_mosaic.out_struct(sums, jnp.float32, bins, g, h, node),
            scratch_shapes=[pltpu.VMEM((features, tile), jnp.int32),
                            pltpu.VMEM(sums, jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(bins, g[None, :], h[None, :], node[None, :])


def unfolded(sums, nodes: int):
    """:func:`level_sums`' ``[features, 256, used_columns(nodes)]``
    whatever the level's layout: a folded level's two halves of the
    columns are its bins' low bit (kilobytes, outside the kernel)."""
    used = used_columns(nodes)
    if not fold(nodes):
        return sums[:, :, :used]
    return sums[:, :, :2 * used].reshape(sums.shape[0], BINS, used)


def level_histograms(bins, g, h, node, nodes: int, *,
                     interpret: Optional[bool] = None):
    """``(hg, hh)``, each ``[nodes, features, 256]`` float32: the sums of
    ``g`` and of ``h`` over the rows of every (node, feature, bin); the
    operands :func:`level_sums`'. The parts' sums added as they lie."""
    sums = unfolded(level_sums(bins, g, h, node, nodes, interpret=interpret), nodes)
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
