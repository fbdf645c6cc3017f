"""Shared whole-run Adam trainer: a ``lax.while_loop`` of psum'd
minibatch steps over a data-sharded mesh.

The scaffold behind MLPClassifier and the factorization machines — any
model whose parameters are a flat tuple of arrays and whose loss is a
per-row weighted sum. The differentiated function contains NO
collectives; local gradient sums are ``psum``'d explicitly and divided
by the global batch weight, which keeps cross-device semantics
unambiguous (no reliance on psum-transpose rules).

Convergence: stop when ``|loss_{t-1} - loss_t| <= tol`` or at
``max_iter`` steps. Minibatch indices come from a per-step
``fold_in``; the key is replicated, so every device samples the same
local row positions of its own (distinct) shard: ``local_bs`` rows drawn
WITH replacement every step. The dense factorization machines, the MLP
and ``survival`` batch so. The factorization machines' fit of a
``CsrColumn`` (``models/_fm_sparse.py``) shares :func:`adam_update` and
not the draw: its step ``t`` reads window ``t mod ceil(rows / batch)`` of
the rows placed in a seeded order, as ``_linear_sgd``'s fits do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _make_minibatch_step(local_loss, axis: str, local_bs: int,
                         n_params: int, frozen_tail: int):
    """ONE Adam minibatch step — the single source of the optimizer math
    shared by the whole-run and chunked trainers (so the streamed fit's
    numerics can never drift from the in-RAM fit's).

    Returns ``step_fn(x, y, w, params, m, v, step, lr, key) ->
    (params, m, v, loss)`` where ``step`` is the GLOBAL 0-based step
    counter (drives both the minibatch key fold and the bias
    correction).
    """

    def step_fn(x, y, w, params, m, v, step, lr, key):
        n_local = x.shape[0]
        k = jax.random.fold_in(key, step)
        idx = jax.random.randint(k, (local_bs,), 0, n_local)
        xb, yb, wb = x[idx], y[idx], w[idx]
        loss_sum, grads = jax.value_and_grad(local_loss)(params, xb, yb, wb)
        total_w = jnp.maximum(jax.lax.psum(jnp.sum(wb), axis), 1e-12)
        loss = jax.lax.psum(loss_sum, axis) / total_w
        grads = jax.tree.map(
            lambda g: jax.lax.psum(g, axis) / total_w, grads
        )
        if frozen_tail:
            grads = tuple(grads[: n_params - frozen_tail]) + tuple(
                jnp.zeros_like(g) for g in grads[n_params - frozen_tail:]
            )
        params, m, v = adam_update(params, m, v, grads, step, lr)
        return params, m, v, loss

    return step_fn


def adam_update(params, m, v, grads, step, lr):
    """Adam's update of a pytree of parameters from the gradients of
    GLOBAL 0-based step ``step``: ``(params, m, v)``. Rates 0.9 and
    0.999, epsilon 1e-8 outside the root, both moments bias-corrected.
    The one statement of it: the dense trainers' step above and the
    sparse factorization machine's (``models/_fm_sparse.py``) call it."""
    t = (step + 1).astype(jnp.float32)
    b1, b2, eps = 0.9, 0.999, 1e-8
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    params = jax.tree.map(
        lambda p, mm, vv: p - lr * (mm / (1 - b1 ** t))
        / (jnp.sqrt(vv / (1 - b2 ** t)) + eps),
        params, m, v,
    )
    return params, m, v


@functools.lru_cache(maxsize=32)
def make_adam_trainer(mesh, axis: str, local_bs: int, loss_builder,
                      n_params: int, frozen_tail: int = 0):
    """``loss_builder`` is a HASHABLE factory (module-level function)
    returning ``loss(params_tuple, xb, yb, wb) -> local weighted sum``.
    Returns a jitted ``trainer(x, y, w, params0, lr, max_iter, tol, key)
    -> (params, steps, loss)``.

    The last ``frozen_tail`` entries of the params tuple are constants
    smuggled through the pytree (e.g. a regularization strength the loss
    reads); their gradients are zeroed so Adam never touches them.
    """
    local_loss = loss_builder()
    mb_step = _make_minibatch_step(local_loss, axis, local_bs, n_params,
                                   frozen_tail)

    def local(x, y, w, params, lr, max_iter, tol, key):
        m0 = jax.tree.map(jnp.zeros_like, params)
        v0 = jax.tree.map(jnp.zeros_like, params)

        def cond(state):
            step, _, _, _, prev, cur = state
            return (step < max_iter) & (jnp.abs(prev - cur) > tol)

        def body(state):
            step, params, m, v, _, last = state
            params, m, v, loss = mb_step(x, y, w, params, m, v, step, lr,
                                         key)
            return step + 1, params, m, v, last, loss

        inf = jnp.asarray(jnp.inf, jnp.float32)
        state = (jnp.asarray(0, jnp.int32), params, m0, v0, inf, -inf)
        step, params, _, _, _, loss = jax.lax.while_loop(cond, body, state)
        return params, step, loss

    flat_specs = tuple(P() for _ in range(n_params))
    return jax.jit(
        jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), flat_specs,
                      P(), P(), P(), P()),
            out_specs=(flat_specs, P(), P()),
        )
    )


@functools.lru_cache(maxsize=32)
def make_adam_chunk_trainer(mesh, axis: str, local_bs: int, loss_builder,
                            n_params: int, frozen_tail: int = 0):
    """Fixed-step sibling of :func:`make_adam_trainer` for streamed
    out-of-core fits: runs ``n_steps`` Adam minibatch steps over ONE
    device-resident chunk, carrying the full optimizer state
    ``(params, m, v, global_step)`` in and out — so the trajectory spans
    every chunk of a replayed cache as one continuous Adam run, and an
    epoch-boundary snapshot of that state resumes bit-exactly.

    Minibatch keys fold the GLOBAL step counter (not a per-chunk index),
    so a resumed run draws exactly the key sequence the uninterrupted
    run would have — the bit-exact-resume requirement. (The rows a key
    selects still live in the resident chunk: minibatches sample within
    the chunk, the classic streamed/sequential-SGD discipline.)
    """
    local_loss = loss_builder()
    mb_step = _make_minibatch_step(local_loss, axis, local_bs, n_params,
                                   frozen_tail)

    def local(x, y, w, params, m, v, step0, lr, n_steps, key):
        def body(_, state):
            params, m, v, step, _ = state
            params, m, v, loss = mb_step(x, y, w, params, m, v, step, lr,
                                         key)
            return params, m, v, step + 1, loss

        state = (params, m, v, step0, jnp.asarray(-jnp.inf, jnp.float32))
        params, m, v, step, loss = jax.lax.fori_loop(
            0, n_steps, body, state
        )
        return params, m, v, step, loss

    flat_specs = tuple(P() for _ in range(n_params))
    return jax.jit(
        jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), flat_specs, flat_specs,
                      flat_specs, P(), P(), P(), P()),
            out_specs=(flat_specs, flat_specs, flat_specs, P(), P()),
        )
    )


def run_streamed_adam(
    source,
    *,
    what: str,
    mesh,
    cache_dir,
    cache_memory_budget_bytes,
    ingest,
    place_y,
    loss_builder,
    n_params: int,
    params0_fn,
    lr: float,
    global_bs: int,
    max_iter: int,
    tol: float,
    seed: int,
    frozen_tail: int = 0,
    checkpoint_manager=None,
    checkpoint_interval: int = 0,
    resume: bool = False,
):
    """The shared out-of-core Adam fit loop (MLP, FM — any
    ``make_adam_trainer`` family member): cache the stream once, then
    each epoch replays the cache chunk-by-chunk through
    :func:`make_adam_chunk_trainer`, with the optimizer state carried
    across chunks as one continuous run and snapshotted at epoch
    boundaries (``begin_resume``/``should_snapshot`` protocol; resume
    requires a durable DataCache input).

    - ``ingest(table) -> {"x", "y", "w"}``: per-batch extraction +
      validation for the caching pass (one-shot stream sources).
    - ``place_y(y_raw) -> y``: label preparation/validation applied at
      replay time (covers sealed-DataCache sources too).
    - ``params0_fn(d) -> flat params tuple``: initial parameters, given
      the feature dim discovered from the cache.

    Chunk policy (the defined contract, not an accident): each resident
    chunk contributes ``ceil(rows / global_bs)`` Adam steps per epoch,
    and chunks pad to the 8p row tile (bounding the set of compiled
    shapes) — so step counts and padded shapes are functions of the
    cache's batch sizes, identical between a fresh run and a resume.

    Returns the final flat params tuple (device arrays).

    Reference parity: ``ReplayOperator.java:62-250`` (replayed cached
    partitions); ``Checkpoints.java:43-211`` (exact-resume contract).
    """
    import numpy as np

    from flinkml_tpu.iteration.checkpoint import begin_resume, should_snapshot
    from flinkml_tpu.iteration.datacache import (
        DataCache,
        DataCacheWriter,
        PrefetchingDeviceFeed,
    )
    from flinkml_tpu.parallel import pad_to_multiple
    from flinkml_tpu.parallel.mesh import DeviceMesh

    # Multi-process: per-process stream partitions + an agreed SPMD
    # schedule. The extra agreement here (vs the linear/KMeans streamed
    # fits) is the per-chunk Adam step count: ``n_steps`` is a traced
    # operand of the chunk trainer and must be identical on every process
    # at every dispatch, so the schedule is derived from the GLOBAL row
    # count of each chunk index (gathered once; the cache is sealed).
    multi = jax.process_count() > 1
    if resume and not isinstance(source, DataCache):
        raise ValueError(
            "resume=True requires a durable DataCache input: a one-shot "
            "stream cannot be replayed from the start after a failure"
        )
    p = mesh.axis_size()
    resume_epoch = begin_resume(checkpoint_manager, resume, mesh.mesh.size)

    # -- pass 0: cache --------------------------------------------------
    from flinkml_tpu.iteration.stream_sync import DeferredValidation

    dv = DeferredValidation()

    first_dim = [None]

    def validate_ingest(t):
        """Full ingest-time validation (zero rows, ragged dims, zero
        total weight) — everything place-time validation would catch,
        because on a multi-process mesh a place-time raise is a
        rank-local abort mid-collective (the hang class
        stream_sync.DeferredValidation exists to prevent)."""
        b = ingest(t)
        x = b["x"]
        if x.shape[0] == 0:
            raise ValueError(
                "stream batch has zero rows; drop empty batches"
            )
        if first_dim[0] is None:
            first_dim[0] = x.shape[1]
        elif x.shape[1] != first_dim[0]:
            raise ValueError(
                f"batch feature dim {x.shape[1]} != first batch's "
                f"{first_dim[0]}"
            )
        if "w" in b and float(np.sum(b["w"])) == 0.0:
            raise ValueError(
                "stream batch has zero total weight (all weights 0); "
                "drop such batches before training"
            )
        return b

    if isinstance(source, DataCache):
        cache = source
    else:
        writer = DataCacheWriter(cache_dir, cache_memory_budget_bytes)

        def ingest_and_append(t):
            # The append is part of the checked step too: a rank-local
            # writer failure (e.g. disk full while spilling a segment)
            # must ride the rendezvous like any ingest failure.
            writer.append(validate_ingest(t))

        from flinkml_tpu.iteration.stream_sync import checked_ingest

        # Multi-process, iterator and ingest failures are held for the
        # post-plan rendezvous (see stream_sync.checked_ingest); a
        # partial cache is fine — the rendezvous aborts every rank
        # before it is consumed.
        for _ in checked_ingest(source, dv, ingest_and_append, multi):
            pass
        cache = writer.finish()
    if not multi and cache.num_rows == 0:
        raise ValueError("training stream is empty")
    d = 0
    if cache.num_batches:
        reader = cache.reader()
        d = np.asarray(next(iter(reader))["x"]).shape[1]
        if hasattr(reader, "close"):
            reader.close()

    plan = None
    nsteps_sched = None
    if multi:
        from flinkml_tpu.iteration.stream_sync import (
            SyncedReplayPlan,
            _entry_rows,
            agree_all_ok,
            agree_feature_dim,
            agree_max,
            gather_vectors,
        )

        # Rendezvous BEFORE planning: a held ingest error must
        # surface as itself, not as plan.create's "stream is empty
        # on every process" (skip-on-failure can leave every local
        # cache empty).
        dv.rendezvous(mesh, "stream ingest validation")
        plan = SyncedReplayPlan.create(cache, mesh, p * 8)
        d = agree_feature_dim(cache, "x", mesh, local_dim=d)
        # Global per-chunk row counts → agreed Adam step schedule.
        local_rows = np.zeros(plan.global_steps)
        for t, entry in enumerate(cache.entries):
            local_rows[t] = _entry_rows(entry)
        rows_global = gather_vectors(local_rows, mesh).sum(axis=0)
        nsteps_sched = np.maximum(
            1, -(-rows_global.astype(np.int64) // global_bs)
        )
        # Agreed label dtype: dummy chunks must dispatch the exact program
        # real chunks do, so their y placeholder needs the real dtype even
        # on a process whose local cache is empty.
        _DTYPE_CODES = {
            np.dtype(np.float32): 1, np.dtype(np.int32): 2,
            np.dtype(np.int64): 3, np.dtype(np.float64): 4,
        }
        _CODE_DTYPES = {v: k for k, v in _DTYPE_CODES.items()}
        local_code = 0
        if cache.num_batches:
            reader = cache.reader()
            y0 = np.asarray(next(iter(reader))["y"])
            if hasattr(reader, "close"):
                reader.close()
            if isinstance(source, DataCache):  # sealed caches: raw labels
                y0 = place_y(y0)
            local_code = _DTYPE_CODES[np.dtype(np.asarray(y0).dtype)]
        code = agree_max(local_code, mesh)
        agree_all_ok(
            not (local_code and local_code != code), mesh,
            "label-dtype agreement",
        )
        y_dtype = _CODE_DTYPES[code]

    # Labels in a cache the runner built itself were already prepared/
    # validated at ingest; re-running place_y per chunk per epoch would
    # put O(rows log rows) redundant host validation on the prefetch
    # thread. Only user-supplied sealed caches need replay-time prep.
    labels_prepared = not isinstance(source, DataCache)
    # Cached batches are immutable, so validation (zero rows/weight,
    # label prep for sealed caches) only needs the FIRST replay pass —
    # not max_iter re-scans on the prefetch thread (the linear stream
    # trainer's first_pass_done discipline).
    first_pass_done = [False]

    def place(batch):
        x = np.asarray(batch["x"], np.float32)
        validate = not first_pass_done[0]
        if validate and x.shape[0] == 0:
            raise ValueError(
                "stream batch has zero rows; drop empty batches"
            )
        if validate and x.shape[1] != d:
            raise ValueError(
                f"batch feature dim {x.shape[1]} != first batch's {d}"
            )
        # Sealed-cache labels need CONVERSION every pass (the cache is
        # re-read from disk each epoch); place_y fuses that with the
        # validation, which is cheap next to the device step.
        y = np.asarray(batch["y"])
        if not labels_prepared:
            y = place_y(y)
        w = (
            np.asarray(batch["w"], np.float32)
            if "w" in batch else np.ones(x.shape[0], np.float32)
        )
        if validate and float(w.sum()) == 0.0:
            # The step normalizes by the batch weight sum; an all-zero
            # chunk would silently train on nothing. Fail loudly (same
            # contract as the linear stream trainer).
            raise ValueError(
                "stream batch has zero total weight (empty batch or all "
                "weights 0); drop such batches before training"
            )
        # 8p row tile bounds the set of padded shapes -> compiles.
        x_pad, n_valid = pad_to_multiple(x, p * 8)
        y_pad, _ = pad_to_multiple(y, p * 8)
        w_pad = np.zeros(x_pad.shape[0], np.float32)
        w_pad[:n_valid] = w[:n_valid]
        return (
            mesh.shard_batch(x_pad), mesh.shard_batch(y_pad),
            mesh.shard_batch(w_pad), x.shape[0],
        )

    def place_multi(batch):
        """Fixed-shape multi-process placement (agreed height; dummy
        chunks are zero-weight no-op contributions to the global step)."""
        height = plan.local_height
        if "_dummy" in batch:
            x_pad = np.zeros((height, d), np.float32)
            y_pad = np.zeros(height, y_dtype)
            w_pad = np.zeros(height, np.float32)
        else:
            x = np.asarray(batch["x"], np.float32)
            if not first_pass_done[0] and x.shape[1] != d:
                raise ValueError(
                    f"batch feature dim {x.shape[1]} != global dim {d}"
                )
            y = np.asarray(batch["y"])
            if not labels_prepared:
                y = place_y(y)
            w = (
                np.asarray(batch["w"], np.float32)
                if "w" in batch else np.ones(x.shape[0], np.float32)
            )
            if not first_pass_done[0] and float(w.sum()) == 0.0:
                raise ValueError(
                    "stream batch has zero total weight (empty batch or "
                    "all weights 0); drop such batches before training"
                )
            from flinkml_tpu.iteration.stream_sync import pad_rows_to

            x_pad = pad_rows_to(x, height, np.float32)
            y_pad = pad_rows_to(np.asarray(y, y_dtype), height)
            w_pad = pad_rows_to(np.asarray(w, np.float32), height)
        return (
            mesh.global_batch(x_pad), mesh.global_batch(y_pad),
            mesh.global_batch(w_pad), 0,
        )

    local_bs = max(1, global_bs // p)
    trainer = make_adam_chunk_trainer(
        mesh.mesh, DeviceMesh.DATA_AXIS, local_bs, loss_builder, n_params,
        frozen_tail,
    )
    flat = tuple(params0_fn(d))
    m = tuple(jnp.zeros_like(t) for t in flat)
    v = tuple(jnp.zeros_like(t) for t in flat)
    step = jnp.asarray(0, jnp.int32)
    sample_key = jax.random.fold_in(jax.random.PRNGKey(seed), 123)
    lr_dev = jnp.asarray(lr, jnp.float32)

    prev_loss = np.inf
    start_epoch = 0
    terminated = False
    mgr = checkpoint_manager
    if resume_epoch is not None:
        like = (
            tuple(np.zeros(t.shape, np.float32) for t in flat),
            tuple(np.zeros(t.shape, np.float32) for t in flat),
            tuple(np.zeros(t.shape, np.float32) for t in flat),
            np.int32(0), np.float64(0.0), np.asarray(False),
        )
        from flinkml_tpu.iteration.stream_sync import agreed_restore

        (flat_h, m_h, v_h, step_h, prev_h, term), start_epoch = (
            agreed_restore(mgr, resume_epoch, like, mesh)
        )
        flat = tuple(jnp.asarray(t) for t in flat_h)
        m = tuple(jnp.asarray(t) for t in m_h)
        v = tuple(jnp.asarray(t) for t in v_h)
        step = jnp.asarray(int(step_h), jnp.int32)
        prev_loss = float(prev_h)
        terminated = bool(term)

    # max_iter counts EPOCHS (one replay pass each); within an epoch
    # every chunk contributes ceil(rows / global_bs) Adam steps.
    from flinkml_tpu.parallel.dispatch import DispatchGuard

    guard = DispatchGuard()  # multi-process backpressure (no-op single)
    for epoch in range(start_epoch, max_iter):
        if terminated:
            break
        last_loss = None
        if multi:
            src = plan.epoch_batches(cache.reader(), lambda: {"_dummy": True})
            feed = PrefetchingDeviceFeed(src, place=place_multi, depth=2)
        else:
            feed = PrefetchingDeviceFeed(cache.reader(), place=place, depth=2)
        try:
            for t, (xb, yb, wb, rows) in enumerate(feed):
                n_steps = (
                    int(nsteps_sched[t]) if multi
                    else max(1, -(-rows // global_bs))  # ceil
                )
                flat, m, v, step, loss = trainer(
                    xb, yb, wb, flat, m, v, step, lr_dev,
                    jnp.asarray(n_steps, jnp.int32), sample_key,
                )
                last_loss = loss
                step = guard.after_dispatch(step)
        finally:
            feed.close()
        guard.flush(step)
        first_pass_done[0] = True  # batches are immutable: validate once
        cur = float(last_loss)
        terminated = abs(prev_loss - cur) <= tol
        prev_loss = cur
        if should_snapshot(mgr, checkpoint_interval, epoch + 1, max_iter,
                           terminal=terminated):
            state = (
                tuple(np.asarray(t) for t in flat),
                tuple(np.asarray(t) for t in m),
                tuple(np.asarray(t) for t in v),
                np.int32(int(step)), np.float64(prev_loss),
                np.asarray(terminated),
            )
            if multi:
                from flinkml_tpu.iteration.checkpoint import save_replicated

                save_replicated(mgr, state, epoch + 1, mesh)
            else:
                mgr.save(state, epoch + 1)
        if terminated:
            break
    return flat
