"""Pallas fused KNN search — the distances' product and their ranking in
ONE kernel, so a block of distances never leaves fast memory.

The tiled XLA search (:func:`flinkml_tpu.models.knn.nearest`) writes
every ``[chunk, tile]`` block of squared distances to HBM and the top-k
kernel reads it back to keep ``k`` of each row: 81 GB a call at the KNN
cell's size. Here the kernel runs once a chunk of query blocks, its grid
over (train block, query block of the chunk): the train blocks in
ascending row order, and INSIDE each the chunk's query blocks, whose
running ``k`` best (the one output block, written back once) stay in
fast memory all the while. A step forms its ``[bq, bt]`` block ``‖q‖² −
2 q·x + ‖x‖²`` (clamped at 0) in VMEM and ranks it there against its
query block's running ``k`` best.

*The product* is the kernel's own (PR 35). A float32 ``v`` is exactly
three bfloat16 parts, ``hi + mid + lo``
(:func:`flinkml_tpu.kernels._split.rounded_parts`), and the float32
product ``q·x`` at ``Precision.HIGHEST`` is the six bfloat16
products a six-pass contraction makes (``q_lo·x_hi``, ``q_mid·x_mid``,
``q_hi·x_lo``, ``q_mid·x_hi``, ``q_hi·x_mid``, ``q_hi·x_hi``: each exact
in float32, summed in float32; the three it drops are under 2⁻²⁴ of the
product). They share their shapes, so they are ONE bfloat16 ``dot``: the
queries' parts side by side along the lanes (made once a chunk, outside
the kernel), the train block's parts one under the other along the
sublanes, the small terms first. At ``d`` 784 that is a contraction of
6 × 784 = 4,704, 37 MXU tiles of 128 where six contractions of 784 take
42. A train block's parts are made at its first query block, once for
all the query blocks of the chunk, into a VMEM scratch: the train set
stays the float32 rows the model holds. ``Precision.DEFAULT`` is one
pass, the ``hi`` parts alone (what the benchmark's control runs); any
other precision is ``jnp.dot``'s on the float32 blocks.

*The ranking*, a block at a time:

  - *the screen*: each row's block minimum against its current ``k``-th
    best distance. A row group (8 rows, one sublane group) with no entry
    STRICTLY under its rows' ``k``-th distances is done: a query block
    meets the train blocks in ascending row order and ties go to the
    lower row, so an entry equal to the ``k``-th best belongs to a higher
    row and stays out. In a stream in no particular order the chance
    that block ``i`` holds an entrant for a row is ≈ k / i: after the
    first few blocks almost every group is done here.
  - *the passes*: the groups that hold an entrant are listed, and taken
    :data:`WAYS` at a time through masked passes, one for each entrant
    and no more: the rows' minimum and its FIRST column, inserted into
    the sorted running best behind every entry not larger (those are
    lower rows), masked out of the block; until no row's minimum is
    under its ``k``-th best. A stream sorted farthest first takes ``k``
    passes a group in every block, and is still exact.

Columns past the last train row (a partial last block, whose padding is
whatever the copy left there) are set to ``+inf`` by their row number,
in that block alone. The answer is ``lax.top_k``'s over the whole row:
the same set, order and ties. Exact: no shortlist, no approximate top-k.

The train set is read as the chip holds it, ``train_x.T`` ([d, n], the
rows along the lanes): a block is a run of lanes, nothing is relaid.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

from flinkml_tpu.kernels._split import rounded_parts

#: Sublanes of a float32 vreg: the rows a pass ranks together.
GROUP = 8
#: Lanes of a vreg: a row's running best lie along one vreg's lanes, so
#: ``k`` is at most this.
LANES = 128
#: Most query rows a block holds, most train rows a block holds (whole
#: :data:`SPLIT_LANES`; the unit the screen decides on), and the row
#: groups whose passes run side by side. Read on a v5e at 10,000 queries
#: against 2,025,000 x 784 rows, k 5 (PERF.md §5; s a call). PR 31, the
#: product Mosaic's ``fp32`` ``dot``, a train block split by it at every
#: step: 1,000 x 2,048 -> 1.407, 1,000 x 4,096 -> 1.226. PR 35, the
#: kernel's own parts: 1,000 x 1,024 -> 1.235, 1,000 x 2,048 -> 1.128,
#: 1,000 x 4,096 -> 1.094, 2,000 x 2,048 -> 1.081, 1,672 x 2,048 -> 1.096
#: (more query rows a block keep the MXU fuller, more train rows are
#: fewer grid steps; neither the split, once a train block, nor the
#: queries' parts streamed at every step shows: 0.0002 and 0.002 s a
#: call; the two before the last need 116 and 120 of the
#: chip's 128 MiB, 1,672 x 2,048 under 100, and compiles in 24 s for
#: their 35-45). Passes one group at a time 121 ms of a call, two 68,
#: four 39, eight 34 (PR 31).
QUERY_BLOCK = 1672
TRAIN_BLOCK = 2048
WAYS = 4
#: Most query blocks a chunk. A chunk is the kernel once: its queries'
#: parts are made for it (10,032 x 4,736 bfloat16, 95 MB), a train block
#: is split into its parts once for all its query blocks, and its running
#: best stay in fast memory (10,032 x 128 float32 and int32: 10.3 MB, two
#: buffers each). 10,000 queries are six blocks of 1,672.
QUERY_BLOCKS = 6
#: Sublanes of a bfloat16 vreg: a part's rows come up to whole tiles.
PART_ROWS = 16
#: Lanes of a sublane tile of train rows split at once: eight float32
#: vregs, whose three parts and what is left between them stay in
#: registers (the whole block at once, through fast memory: + 23 ms a call).
SPLIT_LANES = 512
#: Widest rows the kernel takes: :func:`train_block_rows` still finds
#: them train blocks of 1,536 rows.
MAX_DIM = 1024
#: Fast memory the kernel may use: a v5e has 128 MiB, the compiler's own
#: limit is 16. :func:`train_block_rows` counts 108 MiB at the cell's
#: blocks (Mosaic took under 100).
VMEM_LIMIT_BYTES = 112 * 1024 * 1024


def unsupported_reason(queries, train_x, k: int) -> Optional[str]:
    """Why the fused kernel does not take this search (None = it does)."""
    import jax.numpy as jnp

    if queries.dtype != jnp.float32 or train_x.dtype != jnp.float32:
        return (f"operands {queries.dtype}, {train_x.dtype}: the kernel "
                "forms float32 distances from float32 rows")
    if not 1 <= k <= LANES:
        return f"k={k} outside [1, {LANES}]: a row's running best is one vreg"
    dim = train_x.shape[1]
    if dim > MAX_DIM:
        return f"dim={dim} over {MAX_DIM}: the blocks would not fit fast memory"
    if dim % LANES == 0:
        return (f"dim={dim}, whole vregs: the chip holds such rows along the "
                "sublanes, and blocks cut from train_x.T would relay them all")
    return None


def query_block_rows(n_queries: int, most: int = QUERY_BLOCK) -> int:
    """Query rows a block: the call's rows in the fewest equal blocks of
    at most ``most`` rows, up to a whole row group."""
    blocks = max(1, -(-n_queries // most))
    return max(GROUP, -(-n_queries // (GROUP * blocks)) * GROUP)


def _one_pass(tile, low, best_d, best_r, base, k: int):
    """One masked pass of a row group: where a row's minimum ``low`` of
    its block ``tile`` ([8, bt]) is under its ``k``-th best, the minimum
    and its FIRST column (the lower row of equals) go into the sorted
    running best ([8, LANES]; lanes from ``k`` on are padding and stay
    +inf/0) behind every entry not larger (lower rows, all of them), and
    out of the tile. Returns the four, and the tile's new minima."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    col = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, best_d.shape, 1)
    enters = low < best_d[:, k - 1:k]
    at = jnp.min(jnp.where(tile == low, col, tile.shape[1]), axis=1,
                 keepdims=True)
    stays = best_d <= low
    moved_d = pltpu.roll(best_d, 1, 1)
    moved_r = pltpu.roll(best_r, 1, 1)
    takes = (lane == 0) | (moved_d <= low)
    new_d = jnp.where(stays, best_d, jnp.where(takes, low, moved_d))
    new_r = jnp.where(stays, best_r, jnp.where(takes, base + at, moved_r))
    keep = enters & (lane < k)   # what the shift pushed past k goes
    best_d = jnp.where(keep, new_d, best_d)
    best_r = jnp.where(keep, new_r, best_r)
    tile = jnp.where((col == at) & enters, jnp.inf, tile)
    return tile, jnp.min(tile, axis=1, keepdims=True), best_d, best_r


def _entrants(low, best_d, k: int):
    """1 if any row's minimum is under its k-th best, else 0 (an int32
    scalar: Mosaic carries no bool through a loop)."""
    import jax.numpy as jnp

    return jnp.max(jnp.where(low < best_d[:, k - 1:k], 1, 0))


def _products(precision):
    """The (query part, train part) pairs of ``rounded_parts`` whose
    bfloat16 products, each exact in float32 and summed in float32, are
    the product at ``precision``; the small terms first. ``HIGHEST`` is
    the six XLA's and Mosaic's ``fp32`` contraction make (the three it
    drops are under 2^-24 of the product), ``DEFAULT`` the one pass over
    both operands rounded to bfloat16; None = neither, ``jnp.dot``'s."""
    import jax

    if precision == jax.lax.Precision.HIGHEST:
        return ((2, 0), (1, 1), (0, 2), (1, 0), (0, 1), (0, 0))
    if precision is None or precision == jax.lax.Precision.DEFAULT:
        return ((0, 0),)
    return None


def splits_the_product(precision) -> bool:
    """Whether the kernel makes the product at ``precision`` from several
    bfloat16 parts of its own (``HIGHEST``): not the one pass, not
    ``jnp.dot``'s product."""
    return len(_products(precision) or ()) > 1


def _stacked_width(dim: int, products) -> Tuple[int, int]:
    """``(rows a part takes, length of the one contraction)``: a part's
    ``dim`` rows up to whole bfloat16 sublane tiles, the parts of all
    ``products`` end to end, up to whole MXU tiles."""
    part = -(-dim // PART_ROWS) * PART_ROWS
    return part, -(-len(products) * part // LANES) * LANES


def _search_body(q_ref, qsq_ref, xt_ref, xsq_ref, best_d_ref, best_r_ref,
                 parts_ref, d2_ref, low_ref, todo_ref, *, k: int, dim: int,
                 n_train: int, precision):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    j, i = pl.program_id(0), pl.program_id(1)
    bq, bt = d2_ref.shape
    # This query block's running best, in the chunk's resident ones.
    first = pl.multiple_of(i * bq, GROUP)
    mine = pl.ds(first, bq)

    @pl.when(j == 0)
    def _():
        best_d_ref[mine, :] = jnp.full((bq, LANES), jnp.inf, jnp.float32)
        best_r_ref[mine, :] = jnp.zeros((bq, LANES), jnp.int32)

    def rank_groups(t, _):
        """``WAYS`` listed row groups at once: their passes are chains of
        lane reductions, each waiting for the last, and run side by side."""
        groups = [pl.multiple_of(todo_ref[t * WAYS + w] * GROUP, GROUP)
                  for w in range(WAYS)]
        rows = [(pl.ds(g, GROUP), pl.ds(first + g, GROUP)) for g in groups]
        state = [(d2_ref[r, :], low_ref[r, :], best_d_ref[b, :], best_r_ref[b, :])
                 for r, b in rows]

        def passes(carry):
            state, _ = carry
            state = [_one_pass(*s, j * bt, k) for s in state]
            more = [_entrants(s[1], s[2], k) for s in state]
            return state, functools.reduce(jnp.maximum, more)

        # A listed group holds an entrant: the first pass needs no asking.
        state, _ = jax.lax.while_loop(lambda c: c[1] > 0, passes,
                                      (state, jnp.int32(1)))
        for (_, b), (_, _, best_d, best_r) in zip(rows, state):
            best_d_ref[b, :] = best_d
            best_r_ref[b, :] = best_r
        return 0

    products = _products(precision)
    if products is None:
        product = jnp.dot(q_ref[...], xt_ref[...], precision=precision,
                          preferred_element_type=jnp.float32)
    else:
        part = xt_ref.shape[0]   # dim, up to whole sublane tiles

        @pl.when(i == 0)
        def _():
            # The train block's parts, once for all the query blocks that
            # follow: each product's train part under the one before, whole
            # sublane tiles each, zeros under the last up to whole MXU tiles.
            # A sublane tile of rows and SPLIT_LANES lanes at a time: what
            # the registers hold, so no part goes to fast memory and back.
            def split_rows(t, _):
                at = pl.multiple_of(t * PART_ROWS, PART_ROWS)
                for lo in range(0, bt, SPLIT_LANES):
                    lanes = slice(lo, min(lo + SPLIT_LANES, bt))
                    x = xt_ref[pl.ds(at, PART_ROWS), lanes]
                    if part != dim:   # the block's rows past the array's: anything
                        row = at + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
                        x = jnp.where(row < dim, x, 0.0)
                    x_parts = rounded_parts(x, in_kernel=True)
                    for s, (_, of_x) in enumerate(products):
                        parts_ref[pl.ds(s * part + at, PART_ROWS), lanes] = x_parts[of_x]
                return 0

            jax.lax.fori_loop(0, part // PART_ROWS, split_rows, 0)
            rest = parts_ref.shape[0] - len(products) * part
            if rest:
                parts_ref[len(products) * part:, :] = jnp.zeros((rest, bt),
                                                                jnp.bfloat16)

        product = jnp.dot(q_ref[...], parts_ref[...],
                          preferred_element_type=jnp.float32)
    # ‖q‖² - 2 q·x + ‖x‖², the expansion `nearest` forms.
    d2 = jnp.maximum(qsq_ref[...] - 2.0 * product + xsq_ref[...], 0.0)
    d2_ref[...] = d2
    low_ref[...] = jnp.min(d2, axis=1, keepdims=True)

    @pl.when(j == pl.num_programs(0) - 1)
    def _():
        # A partial last block: its padding holds whatever was there.
        row = j * bt + jax.lax.broadcasted_iota(jnp.int32, (bq, bt), 1)
        d2 = jnp.where(row < n_train, d2_ref[...], jnp.inf)
        d2_ref[...] = d2
        low_ref[...] = jnp.min(d2, axis=1, keepdims=True)

    # The screen's verdicts: the row groups that hold an entrant, listed.
    # Eight groups' verdicts are one read: a group's rows add up in a
    # 4-bit field of their own (8 rows at most, so nothing carries).
    row = jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    field = jnp.left_shift(1, 4 * ((row // GROUP) % 8))
    entrant = jnp.where(low_ref[...] < best_d_ref[mine, k - 1:k], field, 0)
    groups = bq // GROUP
    n = jnp.int32(0)
    for g0 in range(0, groups, 8):
        # (Summed with its dimensions kept: Mosaic lowers a sum to a scalar
        # through jnp.sum again, in the CALLER's mode, int64 under x64.)
        eight = entrant[g0 * GROUP:min(g0 + 8, groups) * GROUP]
        fields = jnp.squeeze(jnp.sum(eight[jnp.newaxis], axis=(1, 2), keepdims=True))
        for g in range(g0, min(g0 + 8, groups)):
            todo_ref[n] = g
            n = n + jnp.minimum((fields >> (4 * (g - g0))) & 15, 1)
    # The list filled up to whole WAYS with its last group: ranked twice
    # side by side, a group is written twice the same.
    for w in range(WAYS - 1):
        todo_ref[n + w] = todo_ref[jnp.maximum(n - 1, 0)]
    jax.lax.fori_loop(0, (n + WAYS - 1) // WAYS, rank_groups, 0)


def train_block_rows(x_rows: int, k_width: int, bq: int, resident: int,
                     most: int) -> int:
    """Train rows a block: ``most``, fewer (whole :data:`SPLIT_LANES`)
    where the kernel's blocks would not fit :data:`VMEM_LIMIT_BYTES`.
    Counted as Mosaic was read to lay them out (PR 35, compiles for a
    described v5e): two buffers of each input and output block (the
    queries' stacked parts, ``k_width`` bfloat16 lanes a row; their norms
    a vreg's lanes wide; the float32 train block of ``x_rows`` rows; the
    ``resident`` rows' running best), the train block's parts, the
    distances and the product they are made from."""
    fixed = 2 * bq * (2 * k_width + 4 * LANES) + 2 * 2 * resident * 4 * LANES
    a_lane = 2 * k_width + 2 * 4 * x_rows + 2 * 4 * bq + 2 * 4 * GROUP
    fits = (VMEM_LIMIT_BYTES - fixed) // a_lane // SPLIT_LANES * SPLIT_LANES
    return max(LANES, min(most, fits))


def fused_nearest(queries, train_x, train_sq, k: int, *, precision,
                  query_block: int = QUERY_BLOCK,
                  train_block: int = TRAIN_BLOCK,
                  query_blocks: int = QUERY_BLOCKS,
                  interpret: Optional[bool] = None) -> Tuple:
    """``(d2, rows)``, both [queries, k]: each query's ``k`` nearest rows
    of ``train_x`` ([n, d] float32, ``train_sq`` its rows' squared norms)
    by (squared distance, row), ties to the lower row, and those
    distances; what ``lax.top_k`` over the whole row of distances gives.

    ``query_block`` and ``train_block`` are the most rows a block holds,
    ``query_blocks`` the most query blocks a chunk (tests pass small
    ones, to cut small searches into many blocks and chunks). Chunks run
    one after the other, each the kernel once: a chunk's stacked parts
    (six bfloat16 copies of its rows) exist while it runs."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from flinkml_tpu.kernels import _mosaic

    if interpret is None:
        interpret = _mosaic.interpret_mode()
    n_queries, dim = queries.shape
    n_train = train_x.shape[0]
    bq = query_block_rows(n_queries, query_block)
    q_blocks = -(-n_queries // bq)
    chunks = -(-q_blocks // query_blocks)
    per_chunk = -(-q_blocks // chunks)
    products = _products(precision)
    if products is None:   # jnp.dot's own: the float32 blocks, no parts
        x_rows, width = dim, 2 * dim   # a float32 row, in bfloat16 lanes
    else:
        x_rows, width = _stacked_width(dim, products)
    bt = min(train_block_rows(x_rows, width, bq, per_chunk * bq, train_block),
             -(-n_train // LANES) * LANES)
    parts_shape = (width, bt) if products else (PART_ROWS, LANES)
    body = functools.partial(_search_body, k=k, dim=dim, n_train=n_train,
                             precision=precision)
    of_queries, of_train = (lambda j, i: (i, 0)), (lambda j, i: (0, j))
    whole = lambda j, i: (0, 0)
    train_t, train_sq = train_x.T, train_sq[None, :]

    def one_chunk(q):
        q_sq = jnp.sum(q * q, axis=-1, keepdims=True)
        if products is not None:
            # The queries' parts side by side, each product's over its
            # train part's rows: the six products are ONE contraction.
            q_parts = rounded_parts(jnp.pad(q, ((0, 0), (0, x_rows - dim))),
                                  in_kernel=False)
            q = jnp.concatenate([q_parts[of_q] for of_q, _ in products], axis=1)
            q = jnp.pad(q, ((0, 0), (0, width - q.shape[1])))
        best_d, best_r = pl.pallas_call(
            body,
            grid=(-(-n_train // bt), per_chunk),
            in_specs=[
                pl.BlockSpec((bq, q.shape[1]), of_queries),
                pl.BlockSpec((bq, 1), of_queries),
                pl.BlockSpec((x_rows, bt), of_train),
                pl.BlockSpec((1, bt), of_train),
            ],
            out_specs=(
                pl.BlockSpec((per_chunk * bq, LANES), whole),
                pl.BlockSpec((per_chunk * bq, LANES), whole),
            ),
            out_shape=(
                _mosaic.out_struct((per_chunk * bq, LANES), jnp.float32, q),
                _mosaic.out_struct((per_chunk * bq, LANES), jnp.int32, q),
            ),
            scratch_shapes=[pltpu.VMEM(parts_shape, jnp.bfloat16),
                            pltpu.VMEM((bq, bt), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.SMEM((bq // GROUP + WAYS,), jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(q, q_sq, train_t, train_sq)
        return best_d[:, :k], best_r[:, :k]

    # Traced in 32-bit mode whatever the caller's (every operand is
    # float32, bfloat16 or int32): Mosaic lowers no 64-bit block index or
    # constant.
    with jax.enable_x64(False):
        padded = chunks * per_chunk * bq
        queries = jnp.pad(queries, ((0, padded - n_queries), (0, 0)))
        best_d, best_r = jax.lax.map(
            one_chunk, queries.reshape(chunks, per_chunk * bq, dim))
    return (best_d.reshape(padded, k)[:n_queries],
            best_r.reshape(padded, k)[:n_queries])
