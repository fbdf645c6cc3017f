"""ALS — alternating least squares matrix factorization (explicit +
implicit feedback).

Beyond the reference snapshot but a flagship member of the wider Flink ML
family (recommendation). ``ALS.fit(Table)`` keeps the ratings on the
mesh with the table and solves a half-step by target block
(``models/_als_blocked.py``: no ``[targets, k, k]`` and no ``[ratings,
k, k]`` array). The STREAMED fit (an iterable of batch tables, a
``DataCache``) is the formulation below, which avoids the
reference-style per-user sequential solves:

  - Each half-step builds every user's normal equations AT ONCE from the
    ratings COO: gather the fixed side's factors (``y = Y[item_idx]``),
    form per-rating outer products, and ``segment_sum`` them into
    ``A [n, k, k]`` / ``b [n, k]`` — one fused scatter per half-step,
    the same keyed-aggregation pattern as NaiveBayes
    (SURVEY.md §2.5 "keyed sharding").
  - The per-rating work is chunked (``lax``-friendly fixed-size blocks)
    so peak memory is ``chunk × k²`` instead of ``nnz × k²``.
  - All user systems solve as ONE batched Cholesky
    (``jax.scipy.linalg.cho_factor/cho_solve`` over ``[n, k, k]``) —
    batched dense linear algebra is exactly what the MXU wants.
  - Multi-device: the COO is sharded over the data axis; per-device
    partial ``A``/``b`` combine with one ``psum`` (inside
    ``keyed_aggregate``), factors are replicated.

Regularization follows ALS-WR (the Spark/Flink convention): λ is scaled
by each user's rating count (``A_u += λ·n_u·I``); users with no ratings
get a pure-λ system and factor 0. Implicit mode is Hu/Koren/Volinsky:
confidence ``c = 1 + α·r``, preference 1 for observed pairs,
``A_u = YᵀY + Σ (c-1) y yᵀ + λ·n_u·I``, ``b_u = Σ c·y``.
"""

from __future__ import annotations

import functools
import os
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from flinkml_tpu.api import Estimator, Model
from flinkml_tpu.models import _als_blocked
from flinkml_tpu.models._als_blocked import GRAM_PRECISION, start_factors  # noqa: F401
from flinkml_tpu.models._streaming import StreamingEstimatorMixin
from flinkml_tpu.common_params import HasMaxIter, HasPredictionCol, HasSeed
from flinkml_tpu.params import (
    BoolParam,
    FloatParam,
    IntParam,
    ParamValidators,
    StringParam,
)
from flinkml_tpu.parallel import DeviceMesh
from flinkml_tpu.table import Table
from flinkml_tpu.utils.profiling import span


class _ALSParams(HasMaxIter, HasPredictionCol, HasSeed):
    USER_COL = StringParam("userCol", "User id column.", "user")
    ITEM_COL = StringParam("itemCol", "Item id column.", "item")
    RATING_COL = StringParam("ratingCol", "Rating column.", "rating")
    RANK = IntParam("rank", "Factor dimensionality.", 10, ParamValidators.gt(0))
    REG_PARAM = FloatParam(
        "regParam", "ALS-WR regularization (scaled by rating count).", 0.1,
        ParamValidators.gt_eq(0.0),
    )
    IMPLICIT_PREFS = BoolParam(
        "implicitPrefs", "Implicit-feedback (confidence-weighted) mode.", False
    )
    ALPHA = FloatParam(
        "alpha", "Implicit-mode confidence slope (c = 1 + alpha * r).", 1.0,
        ParamValidators.gt_eq(0.0),
    )


def _als_layout() -> str:
    """Measured-default gate for the STREAMED fit's normal-equation
    reduction (``ALS.fit(Table)`` reads none of it: it forms no
    per-rating outer product, ``models/_als_blocked.py``).

    ``segment`` (default): per-chunk ``segment_sum`` of the ``[rows, k,
    k]`` outer products — XLA's sort-based lowering drags the 4 KB
    per-row payload through a sort every chunk of every half-step
    (bound in BASELINE.md "Roofline"; its share not measured on the chip).
    ``cumsum``: the rating→target assignment is STATIC across
    iterations, so the in-RAM fit sorts the COO by target once at pack
    time and each chunk reduces at precomputed run boundaries with
    :func:`~flinkml_tpu.ops.sparse.chunked_run_totals` — streaming
    passes plus a runs-sized sorted scatter. ``FLINKML_TPU_ALS_REDUCTION``
    selects; the device A/B decides the default. ``_fit_stream`` itself
    always uses ``segment`` (its chunks come from cache replay,
    unsorted); ``cumsum`` is :func:`_half_step`'s with run tables."""
    layout = os.environ.get("FLINKML_TPU_ALS_REDUCTION")
    if layout is None:
        # Measured default for this mesh (autotune tuning table), else
        # the historical "segment".
        from flinkml_tpu.autotune import tuned_default

        return tuned_default("als_reduction", "segment",
                             allowed=("segment", "cumsum"))
    if layout not in ("segment", "cumsum"):
        raise ValueError(
            f"FLINKML_TPU_ALS_REDUCTION={layout!r}: expected "
            "'segment' or 'cumsum'"
        )
    return layout


def als_run_tables(seg_padded: np.ndarray, p_size: int, chunk: int):
    """Per-(chunk, device) run boundaries for the ``cumsum`` reduction:
    ``(ends, cols)``, each ``[n_chunks, p·max_runs]``, over a COO that
    is PRE-SORTED by segment id (padding ids sort last by construction).
    One :func:`~flinkml_tpu.ops.sparse.run_boundary_tables` call over
    the COO reshaped to one row per (chunk, device) slice."""
    from flinkml_tpu.ops.sparse import run_boundary_tables

    chunk_g = p_size * chunk
    n_chunks = seg_padded.shape[0] // chunk_g
    if n_chunks == 0:  # empty table: zero chunks, zero table rows
        empty = np.zeros((0, 1), np.int32)
        return empty, empty
    ends, cols = run_boundary_tables(
        seg_padded[: n_chunks * chunk_g].reshape(n_chunks * p_size, chunk)
    )
    return (
        ends.reshape(n_chunks, -1),
        cols.reshape(n_chunks, -1),
    )


@functools.lru_cache(maxsize=32)
def _normal_eq_chunk_fn(mesh, axis: str, n_segments: int, implicit: bool,
                        layout: str = "segment"):
    """Accumulate one COO chunk into the normal equations.

    Chunk inputs are sharded over the data axis; the returned partial
    ``A``/``b`` are replicated (local reduction + one psum). Padded
    entries carry segment id ``n_segments`` and fall into a dummy row.
    ``layout="cumsum"`` takes two extra sharded args (per-device run
    ``ends``/``cols`` from :func:`als_run_tables`) and reduces without
    the per-chunk sort (see :func:`_als_layout`).
    """

    def weights(r, alpha):
        if implicit:
            conf_minus_1 = alpha * r
            return conf_minus_1, 1.0 + conf_minus_1  # Σ(c-1)yyᵀ / Σc·y
        return jnp.ones_like(r), r                   # Σyyᵀ / Σr·y

    def local(seg, idx, r, fixed, alpha):
        y = fixed[idx]                  # per-device gather of the fixed side
        a_w, b_w = weights(r, alpha)
        # Padded entries carry seg == n_segments and a_w/b_w of 0 (their
        # rating is 0; explicit a_w=1 is harmless in the dummy row).
        k = y.shape[1]
        outer = (y[:, :, None] * y[:, None, :]) * a_w[:, None, None]
        a = jax.ops.segment_sum(
            outer.reshape(-1, k * k), seg, num_segments=n_segments + 1,
        ).reshape(n_segments + 1, k, k)
        b = jax.ops.segment_sum(b_w[:, None] * y, seg,
                                num_segments=n_segments + 1)
        cnt = jax.ops.segment_sum(jnp.ones_like(r), seg,
                                  num_segments=n_segments + 1)
        return (
            jax.lax.psum(a[:-1], axis),
            jax.lax.psum(b[:-1], axis),
            jax.lax.psum(cnt[:-1], axis),
        )

    def local_cumsum(seg, idx, r, fixed, alpha, ends, cols):
        from flinkml_tpu.ops.sparse import chunked_run_totals

        k = fixed.shape[1]
        rows = seg.shape[0]
        y = fixed[idx]
        a_w, b_w = weights(r, alpha)
        outer = ((y[:, :, None] * y[:, None, :])
                 * a_w[:, None, None]).reshape(rows, k * k)
        payload = jnp.concatenate(
            [outer, b_w[:, None] * y, jnp.ones((rows, 1), y.dtype)], axis=1
        )
        runs = chunked_run_totals(payload, ends)     # [max_runs, k²+k+1]
        a = jnp.zeros((n_segments + 1, k * k), y.dtype).at[cols].add(
            runs[:, : k * k], indices_are_sorted=True
        )
        b = jnp.zeros((n_segments + 1, k), y.dtype).at[cols].add(
            runs[:, k * k: k * k + k], indices_are_sorted=True
        )
        cnt = jnp.zeros((n_segments + 1,), y.dtype).at[cols].add(
            runs[:, -1], indices_are_sorted=True
        )
        return (
            jax.lax.psum(a[:-1].reshape(n_segments, k, k), axis),
            jax.lax.psum(b[:-1], axis),
            jax.lax.psum(cnt[:-1], axis),
        )

    return jax.jit(
        jax.shard_map(
            local_cumsum if layout == "cumsum" else local, mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(), P())
            + ((P(axis), P(axis)) if layout == "cumsum" else ()),
            out_specs=(P(), P(), P()),
        )
    )


@jax.jit
def _solve_factors(a, b, gram, reg, counts):
    """Batched solve of every target's system: (A + gram + λ·max(n,1)·I) x = b.

    λ is floored at 1e-4: with regParam=0 an under-determined row (rating
    count < rank) has a singular system and cho_factor NaN-poisons
    silently; the floor keeps every system SPD within f32 Cholesky
    tolerance (1e-6 still produced NaNs) at negligible bias.
    """
    k = b.shape[1]
    lam = jnp.maximum(reg * jnp.maximum(counts, 1.0), 1e-4)
    eye = jnp.eye(k, dtype=a.dtype)
    systems = a + gram[None, :, :] + lam[:, None, None] * eye[None, :, :]
    cho = jax.scipy.linalg.cho_factor(systems)
    return jax.scipy.linalg.cho_solve(cho, b[:, :, None])[:, :, 0]


def _agree_id_vocab(local_ids: np.ndarray, mesh: DeviceMesh) -> np.ndarray:
    """Union the per-process sorted unique id arrays through the device
    fabric (multi-process streamed fit): each rank's ids ride the
    f64-exact hi/lo transport of
    :func:`~flinkml_tpu.iteration.stream_sync.gather_vectors` (exact for
    integer |id| < 2**47), NaN-padded to the agreed max length; every
    host computes the identical union. Returns int64 when every id is
    integral, float64 otherwise. An empty local vocabulary is legal
    (that rank feeds only dummy chunks)."""
    from flinkml_tpu.iteration.stream_sync import agree_max, gather_vectors

    h = agree_max(int(local_ids.shape[0]), mesh)
    if h == 0:
        raise ValueError("training stream is empty on every process")
    pad = np.full(h, np.nan)
    pad[: local_ids.shape[0]] = np.asarray(local_ids, np.float64)
    rows = gather_vectors(pad, mesh)
    ids = np.unique(rows[np.isfinite(rows)])
    as_int = ids.astype(np.int64)
    if np.array_equal(as_int.astype(np.float64), ids):
        return as_int
    return ids


def _pad_coo(seg: np.ndarray, idx: np.ndarray, r: np.ndarray,
             n_dummy: int, multiple: int):
    """Pad the COO to ``multiple``; padded entries get segment id
    ``n_dummy`` (the dropped dummy row), fixed-side index 0, rating 0 —
    contributing nothing in either mode."""
    pad = (-seg.shape[0]) % multiple
    return (
        np.concatenate([seg, np.full(pad, n_dummy)]).astype(np.int32),
        np.concatenate([idx, np.zeros(pad, idx.dtype)]).astype(np.int32),
        np.concatenate([r, np.zeros(pad, r.dtype)]).astype(np.float32),
    )


def _half_step(
    mesh: DeviceMesh,
    seg: np.ndarray, idx: np.ndarray, r: np.ndarray,   # padded COO (host)
    fixed: jnp.ndarray,            # [m, k] replicated factors of fixed side
    n_target: int,
    reg: float,
    implicit: bool,
    alpha: float,
    chunk: int,
    run_tables=None,
) -> jnp.ndarray:
    """One ALS half-step: solve all n_target factors given the fixed side.

    Chunks of ``devices × chunk`` COO rows stream through the
    normal-equation kernel, bounding the [rows, k, k] intermediate to
    ``chunk × k²`` per device. ``run_tables`` (a list of per-chunk
    device-resident ``(ends, cols)`` pairs from :func:`als_run_tables`,
    over a target-sorted COO) switches the reduction to the sort-free
    ``cumsum`` layout.
    """
    k = fixed.shape[1]
    chunk_g = mesh.axis_size() * chunk
    layout = "segment" if run_tables is None else "cumsum"
    fn = _normal_eq_chunk_fn(
        mesh.mesh, DeviceMesh.DATA_AXIS, n_target, implicit, layout)
    a = jnp.zeros((n_target, k, k), jnp.float32)
    b = jnp.zeros((n_target, k), jnp.float32)
    cnt = jnp.zeros((n_target,), jnp.float32)
    alpha_j = jnp.asarray(alpha, jnp.float32)
    for c in range(seg.shape[0] // chunk_g):
        sl = slice(c * chunk_g, (c + 1) * chunk_g)
        # run_tables entries are per-chunk DEVICE-resident pairs, placed
        # once at fit time (they are iteration-invariant).
        extra = () if run_tables is None else run_tables[c]
        pa, pb, pc = fn(
            mesh.shard_batch(seg[sl]), mesh.shard_batch(idx[sl]),
            mesh.shard_batch(r[sl]), fixed, alpha_j, *extra,
        )
        a, b, cnt = a + pa, b + pb, cnt + pc
    if implicit:
        gram = fixed.T @ fixed
    else:
        gram = jnp.zeros((k, k), jnp.float32)
    return _solve_factors(a, b, gram, jnp.asarray(reg, jnp.float32), cnt)


def coo_fit(u_idx, i_idx, ratings, n_users: int, n_items: int, rank: int,
            max_iter: int, reg: float, implicit: bool = False,
            alpha: float = 1.0, seed: int = 0, mesh: Optional[DeviceMesh] = None,
            chunk: int = 1 << 16):
    """``(user_factors, item_factors)`` by the STREAMED fit's formulation
    over a COO held in RAM: ``max_iter`` pairs of :func:`_half_step`
    under :func:`_als_layout`'s reduction. No estimator runs it
    (``ALS.fit(Table)`` is ``models/_als_blocked.py``); it is what the
    reduction's A/B (``tools/als_reduction_probe.py``, the autotune knob
    ``als_reduction``) and the tests of the gated pair measure."""
    mesh = mesh or DeviceMesh()
    ratings = np.asarray(ratings, np.float32)
    chunk = min(chunk, max(256, -(-len(ratings) // mesh.axis_size())))
    chunk_g = mesh.axis_size() * chunk
    rng = np.random.default_rng(seed)
    item_f = jnp.asarray(
        rng.normal(scale=1.0 / np.sqrt(rank), size=(n_items, rank))
        .astype(np.float32))
    user_tabs = item_tabs = None
    if _als_layout() == "cumsum":
        # Sort each side by target ONCE (the assignment is static across
        # iterations); padding ids (n_targets) sort last by construction,
        # so _pad_coo keeps the order.
        ou = np.argsort(u_idx, kind="stable")
        oi = np.argsort(i_idx, kind="stable")
        by_user = _pad_coo(u_idx[ou], i_idx[ou], ratings[ou], n_users, chunk_g)
        by_item = _pad_coo(i_idx[oi], u_idx[oi], ratings[oi], n_items, chunk_g)

        def place_tabs(tabs):
            # The iteration-invariant tables, placed once, a sharded
            # pair a chunk.
            return [(mesh.shard_batch(e), mesh.shard_batch(c))
                    for e, c in zip(*tabs)]

        user_tabs = place_tabs(als_run_tables(by_user[0], mesh.axis_size(), chunk))
        item_tabs = place_tabs(als_run_tables(by_item[0], mesh.axis_size(), chunk))
    else:
        by_user = _pad_coo(u_idx, i_idx, ratings, n_users, chunk_g)
        by_item = _pad_coo(i_idx, u_idx, ratings, n_items, chunk_g)
    for _ in range(max_iter):
        user_f = _half_step(mesh, *by_user, item_f, n_users, reg, implicit,
                            alpha, chunk, run_tables=user_tabs)
        item_f = _half_step(mesh, *by_item, user_f, n_items, reg, implicit,
                            alpha, chunk, run_tables=item_tabs)
    return np.asarray(user_f), np.asarray(item_f)


class ALS(StreamingEstimatorMixin, _ALSParams, Estimator):
    """Alternating least squares over (user, item, rating) tables.

    ``fit`` accepts, besides a single in-RAM :class:`Table`:

      - an **iterable of batch Tables** — the out-of-core path: the COO
        stream is cached once (spilling to ``cache_dir`` beyond
        ``cache_memory_budget_bytes``) while the id vocabularies
        accumulate; every half-step then replays the cache, building the
        target side's normal equations batch-by-batch with bounded HBM
        residency (reference: ``ReplayOperator.java:62-250`` — every
        bounded iteration trains from replayed cached partitions);
      - a sealed :class:`~flinkml_tpu.iteration.datacache.DataCache`
        whose batches carry this estimator's user/item/rating columns.

    ``checkpoint_manager`` + ``checkpoint_interval`` snapshot
    ``(user_factors, item_factors)`` every N outer iterations of the
    streamed fit; ``resume=True`` restores and continues bit-exactly.
    """

    # Per-device rows the STREAMED fit hands to one normal-equation
    # dispatch; bounds its nnz×k² intermediate to chunk×k² per device.
    CHUNK = 1 << 16

    #: The knob is ACCEPTED at construction so the fit-time refusal can
    #: explain WHY the embedding-sharded primitive does not apply to
    #: ALS training (see :meth:`_refuse_sharded_fit`), instead of the
    #: mixin's generic constructor refusal.
    _SHARDING_PLAN_AWARE = True

    def _refuse_sharded_fit(self) -> None:
        """An embedding-sharded plan shards factor STORAGE; neither fit
        stores its factors that way while it trains. The table fit
        (``models/_als_blocked.py``) deals the TARGETS over the devices
        and keeps the fixed side replicated on each (every device reads
        any row of it); the streamed fit scatters into vocab-sized
        ``A [n, k, k]`` / ``b [n, k]`` buffers. Refuse loudly — the
        honest wiring — and point at what DOES exist:
        :meth:`ALSModel.factor_tables` serves fitted factors sharded."""
        if self.sharding_plan is not None:
            raise ValueError(
                "ALS.fit does not thread a sharding_plan: a half-step "
                "reads ANY row of the fixed side's factors, so the table "
                "fit keeps both factor tables replicated on every device "
                "(it deals the targets, not the rows, over the mesh), and "
                "the streamed fit's normal-equation buffers (A [n, k, k] "
                "/ b [n, k]) are vocab-sized however the factors shard. "
                "An embedding-sharded plan would not cap the working set "
                "it promises to cap. Fitted factors CAN be served "
                "sharded — see ALSModel.factor_tables and "
                "docs/development/embeddings.md."
            )

    def fit(self, *inputs) -> "ALSModel":
        self._refuse_sharded_fit()
        (table,) = inputs
        if not isinstance(table, Table):
            return self._fit_stream(table)
        self._reject_in_ram_checkpointing()
        with span("fit"):
            model = ALSModel()
            model.copy_params_from(self)
            model._set_factors(*_als_blocked.fit_table(self, table))
            return model

    def _fit_stream(self, source) -> "ALSModel":
        """Out-of-core ALS (see class docstring): one caching pass
        accumulates the sorted id vocabularies; each half-step replays
        the cache, padding every batch to the row tile and accumulating
        the psum'd normal-equation partials on device. Only one batch
        (plus prefetch depth) of the COO is device-resident at a time.

        Multi-process (round 4): each process feeds its own ratings
        partition; the id vocabularies are unioned through the device
        fabric (numeric ids, |id| < 2**47 — :func:`_agree_id_vocab`),
        the per-half-step chunk schedule is agreed (drained ranks
        dispatch all-sentinel dummy chunks — exact no-ops, every row
        lands in the dropped segment), ingest failures ride the
        held-error rendezvous, dispatches are bounded, and the
        replicated factor pair checkpoints rank-0-write + barrier."""
        from flinkml_tpu.iteration.checkpoint import (
            begin_resume,
            should_snapshot,
        )
        from flinkml_tpu.iteration.datacache import (
            DataCache,
            DataCacheWriter,
            PrefetchingDeviceFeed,
        )
        from flinkml_tpu.iteration.stream_sync import (
            DeferredValidation,
            checked_ingest,
        )

        multi = jax.process_count() > 1
        if self.resume and not isinstance(source, DataCache):
            raise ValueError(
                "resume=True requires a durable DataCache input: a one-shot "
                "stream cannot be replayed from the start after a failure"
            )
        user_col = self.get(self.USER_COL)
        item_col = self.get(self.ITEM_COL)
        rating_col = self.get(self.RATING_COL)
        implicit = self.get(self.IMPLICIT_PREFS)
        rank = self.get(self.RANK)
        reg = self.get(self.REG_PARAM)
        alpha = self.get(self.ALPHA)
        mesh = self.mesh or DeviceMesh()
        resume_epoch = begin_resume(
            self.checkpoint_manager, self.resume, mesh.mesh.size
        )

        # -- pass 0: cache + per-batch uniques (one global sort at the end:
        # union1d per batch would re-sort the whole vocabulary B times) ----
        user_parts = []
        item_parts = []
        nnz = 0

        def ingest(u, i, r):
            nonlocal nnz
            if not (u.shape[0] == i.shape[0] == r.shape[0]):
                raise ValueError(
                    "user/item/rating columns must have equal length, got "
                    f"{u.shape[0]}/{i.shape[0]}/{r.shape[0]}"
                )
            if implicit and (r < 0).any():
                raise ValueError(
                    "implicitPrefs requires non-negative ratings"
                )
            if multi:
                for arr, what in ((u, "user"), (i, "item")):
                    ok = np.issubdtype(arr.dtype, np.number)
                    if ok:
                        a64 = np.asarray(arr, np.float64)
                        ok = bool(
                            np.all(np.isfinite(a64))
                            and (a64.size == 0
                                 or np.abs(a64).max() < 2.0 ** 47)
                        )
                    if not ok:
                        raise ValueError(
                            "multi-process ALS streamed fit requires "
                            f"finite numeric {what} ids with |id| < 2**47 "
                            "(they are unioned exactly through the "
                            "device fabric's f64 hi/lo transport)"
                        )
            user_parts.append(np.unique(u))
            item_parts.append(np.unique(i))
            nnz += r.shape[0]

        def batch_arrays(b):
            if isinstance(b, Table):
                return (
                    np.asarray(b.column(user_col)),
                    np.asarray(b.column(item_col)),
                    np.asarray(b.column(rating_col), np.float32),
                )
            return (
                np.asarray(b[user_col]),
                np.asarray(b[item_col]),
                np.asarray(b[rating_col], np.float32),
            )

        dv = DeferredValidation()

        def checked_add(b):
            # Extraction + validation are one checked step; multi-process
            # failures (and iterator raises) are held for the rendezvous.
            ingest(*batch_arrays(b))

        if isinstance(source, DataCache):
            cache = source
            for _ in checked_ingest(cache.reader(), dv, checked_add, multi):
                pass
        else:
            writer = DataCacheWriter(
                self.cache_dir, self.cache_memory_budget_bytes
            )

            def add_append(b):
                u, i, r = batch_arrays(b)
                ingest(u, i, r)
                # The append is part of the checked step too (a rank-local
                # spill failure must ride the rendezvous).
                writer.append({user_col: np.array(u), item_col: np.array(i),
                               rating_col: np.array(r)})

            for _ in checked_ingest(source, dv, add_append, multi):
                pass
            cache = writer.finish()

        def local_unique(parts):
            return (
                np.unique(np.concatenate(parts)) if parts else np.empty(0)
            )

        if multi:
            from flinkml_tpu.iteration.stream_sync import gather_vectors

            # Rendezvous BEFORE any agreement: a held ingest error must
            # surface as itself, not as "stream is empty".
            dv.rendezvous(mesh, "stream ingest validation")
            nnz = int(round(gather_vectors(
                np.asarray([float(nnz)]), mesh
            ).sum()))
            if nnz == 0:
                raise ValueError("training stream is empty on every process")
            user_ids = _agree_id_vocab(local_unique(user_parts), mesh)
            item_ids = _agree_id_vocab(local_unique(item_parts), mesh)
        else:
            if nnz == 0:
                raise ValueError("training stream is empty")
            user_ids = local_unique(user_parts)
            item_ids = local_unique(item_parts)
        n_users, n_items = len(user_ids), len(item_ids)

        # Replayed batches dispatch in FIXED chunk_local-row slices (this
        # process's share of one dispatch) — the same CHUNK bound the
        # in-RAM path uses to cap the [rows, k, k] normal-equation
        # intermediate at chunk×k² per device, and a single compiled
        # shape per target side regardless of how the cache happens to
        # be batched. Under multi-process, nnz is the GLOBAL count
        # (agreed above), so every rank compiles the same chunk shape.
        chunk = min(self.CHUNK, max(256, -(-nnz // mesh.axis_size())))
        chunk_local = (mesh.axis_size() // jax.process_count()) * chunk

        steps_half = None
        if multi:
            from flinkml_tpu.iteration.stream_sync import (
                agree_max,
                entry_rows,
            )

            # Agreed chunk schedule per half-step: every rank dispatches
            # the same number of chunk calls; drained ranks fill with
            # all-sentinel dummy chunks (exact no-ops — every padded row
            # lands in the dropped dummy segment).
            local_total = sum(
                -(-entry_rows(e) // chunk_local) for e in cache.entries
            )
            steps_half = agree_max(local_total, mesh)

        chunk_fns = {
            True: _normal_eq_chunk_fn(
                mesh.mesh, DeviceMesh.DATA_AXIS, n_users, implicit, "segment"),
            False: _normal_eq_chunk_fn(
                mesh.mesh, DeviceMesh.DATA_AXIS, n_items, implicit, "segment"),
        }
        alpha_j = jnp.asarray(alpha, jnp.float32)

        from flinkml_tpu.parallel.dispatch import DispatchGuard

        def replay_half(fixed, by_user: bool):
            """One half-step's accumulation over the replayed cache."""
            n_target = n_users if by_user else n_items
            k = fixed.shape[1]
            a = jnp.zeros((n_target, k, k), jnp.float32)
            bvec = jnp.zeros((n_target, k), jnp.float32)
            cnt = jnp.zeros((n_target,), jnp.float32)
            fn = chunk_fns[by_user]
            guard = DispatchGuard()  # multi-process backpressure

            def place(batch):
                u, i, r = batch_arrays(batch)
                u_idx = np.searchsorted(user_ids, u).astype(np.int32)
                i_idx = np.searchsorted(item_ids, i).astype(np.int32)
                seg, idx = (u_idx, i_idx) if by_user else (i_idx, u_idx)
                seg, idx, r = _pad_coo(seg, idx, r, n_target, chunk_local)
                return [
                    (
                        mesh.global_batch(seg[sl]), mesh.global_batch(idx[sl]),
                        mesh.global_batch(r[sl]),
                    )
                    for sl in (
                        slice(c * chunk_local, (c + 1) * chunk_local)
                        for c in range(seg.shape[0] // chunk_local)
                    )
                ]

            dispatched = 0
            feed = PrefetchingDeviceFeed(cache.reader(), place=place, depth=2)
            try:
                for chunks in feed:
                    for seg, idx, r in chunks:
                        if steps_half is not None and dispatched >= steps_half:
                            raise RuntimeError(
                                "local cache yielded more chunks than the "
                                "agreed schedule — caches must be sealed "
                                "before planning"
                            )
                        pa, pb, pc = fn(seg, idx, r, fixed, alpha_j)
                        a, bvec, cnt = a + pa, bvec + pb, cnt + pc
                        dispatched += 1
                        guard.after_dispatch(cnt)
            finally:
                feed.close()
            if steps_half is not None and dispatched < steps_half:
                # Drained before the agreed schedule: dummy chunks keep
                # the SPMD dispatch count aligned across ranks.
                dseg = mesh.global_batch(
                    np.full(chunk_local, n_target, np.int32)
                )
                didx = mesh.global_batch(np.zeros(chunk_local, np.int32))
                dr = mesh.global_batch(np.zeros(chunk_local, np.float32))
                while dispatched < steps_half:
                    pa, pb, pc = fn(dseg, didx, dr, fixed, alpha_j)
                    a, bvec, cnt = a + pa, bvec + pb, cnt + pc
                    dispatched += 1
                    guard.after_dispatch(cnt)
            guard.flush(cnt)
            if implicit:
                gram = fixed.T @ fixed
            else:
                gram = jnp.zeros((k, k), jnp.float32)
            return _solve_factors(
                a, bvec, gram, jnp.asarray(reg, jnp.float32), cnt
            )

        user_f = jnp.zeros((n_users, rank), jnp.float32)
        start_epoch = 0
        if resume_epoch is None:
            rng = np.random.default_rng(self.get_seed())
            item_f = jnp.asarray(
                rng.normal(scale=1.0 / np.sqrt(rank), size=(n_items, rank))
                .astype(np.float32)
            )
        else:
            item_f = jnp.zeros((n_items, rank), jnp.float32)  # restored below
            like = (np.zeros((n_users, rank), np.float32),
                    np.zeros((n_items, rank), np.float32))
            from flinkml_tpu.iteration.stream_sync import agreed_restore

            (user_h, item_h), start_epoch = agreed_restore(
                self.checkpoint_manager, resume_epoch, like, mesh
            )
            user_f = jnp.asarray(user_h)
            item_f = jnp.asarray(item_h)

        max_iter = self.get(self.MAX_ITER)
        for epoch in range(start_epoch, max_iter):
            user_f = replay_half(item_f, by_user=True)
            item_f = replay_half(user_f, by_user=False)
            if should_snapshot(self.checkpoint_manager,
                               self.checkpoint_interval, epoch + 1, max_iter):
                state = (np.asarray(user_f), np.asarray(item_f))
                if multi:
                    from flinkml_tpu.iteration.checkpoint import (
                        save_replicated,
                    )

                    save_replicated(
                        self.checkpoint_manager, state, epoch + 1, mesh
                    )
                else:
                    self.checkpoint_manager.save(state, epoch + 1)

        model = ALSModel()
        model.copy_params_from(self)
        model._set_factors(
            user_ids, np.asarray(user_f), item_ids, np.asarray(item_f)
        )
        return model


class ALSModel(_ALSParams, Model):
    def __init__(self):
        super().__init__()
        self._user_ids: Optional[np.ndarray] = None
        self._item_ids: Optional[np.ndarray] = None
        self._user_factors: Optional[np.ndarray] = None
        self._item_factors: Optional[np.ndarray] = None

    def _set_factors(self, user_ids, user_factors, item_ids, item_factors):
        """The factors as handed over: a fit's are the float32 the chip
        returned, and are widened when first asked for."""
        self._user_ids = np.asarray(user_ids)
        self._item_ids = np.asarray(item_ids)
        self._user_factors = np.asarray(user_factors)
        self._item_factors = np.asarray(item_factors)

    def factors(self) -> Tuple[np.ndarray, np.ndarray]:
        """``(user [users, rank], item [items, rank])`` as the model holds
        them (a fit's float32, nothing copied)."""
        self._require()
        return self._user_factors, self._item_factors

    @property
    def user_factors(self) -> np.ndarray:
        self._require()
        if self._user_factors.dtype != np.float64:
            self._user_factors = self._user_factors.astype(np.float64)
        return self._user_factors

    @property
    def item_factors(self) -> np.ndarray:
        self._require()
        if self._item_factors.dtype != np.float64:
            self._item_factors = self._item_factors.astype(np.float64)
        return self._item_factors

    def set_model_data(self, *inputs: Table) -> "ALSModel":
        user_t, item_t = inputs
        self._set_factors(
            user_t.column("id"), user_t.column("factors"),
            item_t.column("id"), item_t.column("factors"),
        )
        return self

    def get_model_data(self) -> List[Table]:
        self._require()
        return [
            Table({"id": self._user_ids, "factors": self.user_factors}),
            Table({"id": self._item_ids, "factors": self.item_factors}),
        ]

    def _require(self) -> None:
        if self._user_factors is None:
            raise ValueError("Model data is not set; fit or set_model_data first")

    def _positions(self, raw: np.ndarray, ids: np.ndarray):
        order = np.argsort(ids, kind="stable")
        sorted_ids = ids[order]
        pos = np.searchsorted(sorted_ids, raw)
        pos_c = np.minimum(pos, len(ids) - 1)
        found = sorted_ids[pos_c] == raw
        return order[pos_c], found

    def transform(self, *inputs: Table) -> Tuple[Table, ...]:
        """Predict ratings for (user, item) rows; unseen ids → NaN (the
        upstream 'nan' cold-start strategy)."""
        (table,) = inputs
        self._require()
        users = np.asarray(table.column(self.get(self.USER_COL)))
        items = np.asarray(table.column(self.get(self.ITEM_COL)))
        u_pos, u_ok = self._positions(users, self._user_ids)
        i_pos, i_ok = self._positions(items, self._item_ids)
        pred = np.einsum(
            "nk,nk->n", self.user_factors[u_pos], self.item_factors[i_pos]
        )
        pred = np.where(u_ok & i_ok, pred, np.nan)
        return (table.with_column(self.get(self.PREDICTION_COL), pred),)

    def factor_tables(self, mesh=None, plan=None,
                      hbm_budget_bytes=None):
        """The fitted factors as row-sharded
        :class:`~flinkml_tpu.embeddings.EmbeddingTable`\\ s
        ``(user_table, item_table)`` — the serving-scale export: a
        100M-user factor matrix that cannot replicate onto one chip
        serves sharded (``table.lookup`` is bitwise stable at every
        world size, and an
        :class:`~flinkml_tpu.embeddings.serving.EmbeddingLookupModel`
        built from ``model.item_factors`` rides the ReplicaPool's slice
        meshes). Plan/budget resolution is EmbeddingTable's (explicit
        plan > ``infer_plan`` under a budget > replicated)."""
        from flinkml_tpu.embeddings import EmbeddingTable

        self._require()
        kw = dict(mesh=mesh, plan=plan, hbm_budget_bytes=hbm_budget_bytes)
        return (
            EmbeddingTable(
                "als/user", *self._user_factors.shape,
                rows=self._user_factors.astype(np.float32), **kw,
            ),
            EmbeddingTable(
                "als/item", *self._item_factors.shape,
                rows=self._item_factors.astype(np.float32), **kw,
            ),
        )

    def recommend_for_all_users(self, num_items: int):
        """Top ``num_items`` items per user: one [users, k] @ [k, items]
        matmul + top_k on device (the MXU path). Returns
        (item_id_matrix [n_users, num_items], score_matrix)."""
        self._require()
        scores = jnp.asarray(self._user_factors, jnp.float32) @ jnp.asarray(
            self._item_factors, jnp.float32
        ).T
        vals, idx = jax.lax.top_k(scores, min(num_items, len(self._item_ids)))
        return self._item_ids[np.asarray(idx)], np.asarray(vals)

    def save(self, path: str) -> None:
        self._require()
        self._save_with_arrays(path, {
            "userIds": self._user_ids,
            "userFactors": self.user_factors,
            "itemIds": self._item_ids,
            "itemFactors": self.item_factors,
        })

    @classmethod
    def load(cls, path: str) -> "ALSModel":
        model, arrays, _ = cls._load_with_arrays(path)
        model._set_factors(
            arrays["userIds"], arrays["userFactors"],
            arrays["itemIds"], arrays["itemFactors"],
        )
        return model
