"""Pallas fused KNN search — the distances' product and their ranking in
ONE kernel, so a block of distances never leaves fast memory.

The tiled XLA search (:func:`flinkml_tpu.models.knn.nearest`) writes
every ``[chunk, tile]`` block of squared distances to HBM and the top-k
kernel reads it back to keep ``k`` of each row: 81 GB a call at the KNN
cell's size. Here the grid runs over (query block, train block), the
train blocks innermost and in ascending row order; a step forms its
``[bq, bt]`` block ``‖q‖² − 2 q·x + ‖x‖²`` (clamped at 0) in VMEM from a
``[bq, d] @ [d, bt]`` product at the caller's precision, and ranks it
there against the query block's running ``k`` best, which live in the
output blocks (revisited along the train axis, written back once):

  - *the screen*: each row's block minimum against its current ``k``-th
    best distance. A row group (8 rows, one sublane group) with no entry
    STRICTLY under its rows' ``k``-th distances is done: train blocks
    come in ascending row order and ties go to the lower row, so an
    entry equal to the ``k``-th best belongs to a higher row and stays
    out. In a stream in no particular order the chance that block ``i``
    holds an entrant for a row is ≈ k / i: after the first few blocks
    almost every group is done here.
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

#: Sublanes of a float32 vreg: the rows a pass ranks together.
GROUP = 8
#: Lanes of a vreg: a row's running best lie along one vreg's lanes, so
#: ``k`` is at most this.
LANES = 128
#: Most query rows a block holds, most train rows a block holds (a
#: multiple of :data:`LANES`; the unit the screen decides on), and the
#: row groups whose passes run side by side. Read on a v5e at 10,000
#: queries against 2,025,000 x 784 rows, k 5 (PERF.md §5, PR 31; s a
#: call): 504 x 2,048 -> 1.535, 1,000 x 2,048 -> 1.407 (the product
#: splits its train block into bfloat16 parts once a step, whatever the
#: query rows that share it), 1,000 x 4,096 -> 1.345, 1,672 x 4,096 ->
#: 1.208 at half as much again to compile; passes one group at a time
#: 121 ms of a call, two 68, four 39, eight 34.
QUERY_BLOCK = 1024
TRAIN_BLOCK = 4096
WAYS = 4
#: Widest rows the kernel takes: two [dim, TRAIN_BLOCK] train blocks (the
#: pipeline's), two query blocks and two [QUERY_BLOCK, TRAIN_BLOCK]
#: blocks of distances have to fit :data:`VMEM_LIMIT_BYTES`.
MAX_DIM = 1024
#: Fast memory the kernel may use: a v5e has 128 MiB, the compiler's own
#: limit is 16. At 784-wide rows the blocks take ≈ 75 MiB.
VMEM_LIMIT_BYTES = 96 * 1024 * 1024


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


def _search_body(q_ref, qsq_ref, xt_ref, xsq_ref, best_d_ref, best_r_ref,
                 d2_ref, low_ref, todo_ref, *, k: int, n_train: int, precision):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    j = pl.program_id(1)
    bq, bt = d2_ref.shape

    @pl.when(j == 0)
    def _():
        best_d_ref[...] = jnp.full(best_d_ref.shape, jnp.inf, jnp.float32)
        best_r_ref[...] = jnp.zeros(best_r_ref.shape, jnp.int32)

    def rank_groups(t, _):
        """``WAYS`` listed row groups at once: their passes are chains of
        lane reductions, each waiting for the last, and run side by side."""
        rows = [pl.ds(pl.multiple_of(todo_ref[t * WAYS + w] * GROUP, GROUP), GROUP)
                for w in range(WAYS)]
        state = [(d2_ref[r, :], low_ref[r, :], best_d_ref[r, :], best_r_ref[r, :])
                 for r in rows]

        def passes(carry):
            state, _ = carry
            state = [_one_pass(*s, j * bt, k) for s in state]
            more = [_entrants(s[1], s[2], k) for s in state]
            return state, functools.reduce(jnp.maximum, more)

        # A listed group holds an entrant: the first pass needs no asking.
        state, _ = jax.lax.while_loop(lambda c: c[1] > 0, passes,
                                      (state, jnp.int32(1)))
        for r, (_, _, best_d, best_r) in zip(rows, state):
            best_d_ref[r, :] = best_d
            best_r_ref[r, :] = best_r
        return 0

    # ‖q‖² - 2 q·x + ‖x‖², the expansion `nearest` forms.
    product = jnp.dot(q_ref[...], xt_ref[...], precision=precision,
                      preferred_element_type=jnp.float32)
    d2 = jnp.maximum(qsq_ref[...] - 2.0 * product + xsq_ref[...], 0.0)
    d2_ref[...] = d2
    low_ref[...] = jnp.min(d2, axis=1, keepdims=True)

    @pl.when(j == pl.num_programs(1) - 1)
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
    entrant = jnp.where(low_ref[...] < best_d_ref[:, k - 1:k], field, 0)
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


def fused_nearest(queries, train_x, train_sq, k: int, *, precision,
                  query_block: int = QUERY_BLOCK,
                  train_block: int = TRAIN_BLOCK,
                  interpret: Optional[bool] = None) -> Tuple:
    """``(d2, rows)``, both [queries, k]: each query's ``k`` nearest rows
    of ``train_x`` ([n, d] float32, ``train_sq`` its rows' squared norms)
    by (squared distance, row), ties to the lower row, and those
    distances; what ``lax.top_k`` over the whole row of distances gives.

    ``query_block`` and ``train_block`` are the most rows a block holds
    (tests pass small ones, to cut small searches into many blocks)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from flinkml_tpu.kernels import _gate

    if interpret is None:
        interpret = _gate.interpret_mode()
    n_queries, dim = queries.shape
    n_train = train_x.shape[0]
    bq = query_block_rows(n_queries, query_block)
    q_blocks = -(-n_queries // bq)
    bt = min(train_block, -(-n_train // LANES) * LANES)
    queries = jnp.pad(queries, ((0, q_blocks * bq - n_queries), (0, 0)))
    q_sq = jnp.sum(queries * queries, axis=-1, keepdims=True)
    body = functools.partial(_search_body, k=k, n_train=n_train,
                             precision=precision)
    # Traced in 32-bit mode whatever the caller's (every operand is
    # float32 or int32): Mosaic lowers no 64-bit block index or constant.
    with jax.enable_x64(False):
        best_d, best_r = pl.pallas_call(
            body,
            grid=(q_blocks, -(-n_train // bt)),
            in_specs=[
                pl.BlockSpec((bq, dim), lambda i, j: (i, 0)),
                pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((dim, bt), lambda i, j: (0, j)),
                pl.BlockSpec((1, bt), lambda i, j: (0, j)),
            ],
            out_specs=(
                pl.BlockSpec((bq, LANES), lambda i, j: (i, 0)),
                pl.BlockSpec((bq, LANES), lambda i, j: (i, 0)),
            ),
            out_shape=(
                _gate.out_struct((q_blocks * bq, LANES), jnp.float32, queries),
                _gate.out_struct((q_blocks * bq, LANES), jnp.int32, queries),
            ),
            scratch_shapes=[pltpu.VMEM((bq, bt), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.SMEM((bq // GROUP + WAYS,), jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(queries, q_sq, train_x.T, train_sq[None, :])
    return best_d[:n_queries, :k], best_r[:n_queries, :k]
