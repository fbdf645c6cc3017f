"""Word2Vec — skip-gram with negative sampling (the Spark/Flink family
member), TPU-native.

``fit(Table)`` is :mod:`flinkml_tpu.models._w2v_table`: the token column
(a :class:`~flinkml_tpu.table.TokenColumn`, or an object column of token
lists encoded to one) ingested once a table and kept on the chip, the
pairs and the negatives drawn on the device, a step the update of the
batch's rows — one program, ``w2v_sgns_loop``, on one device; on more,
:func:`_sgns_trainer_sharded` over the same draw. What follows is the
STREAMED fit (an iterable of batch Tables).

Host prep (strings never touch the device): frequency vocabulary with
``minCount`` pruning, (center, context) pair generation over
``windowSize``, and a unigram^0.75 negative-sampling pool materialized
as a flat int array (sampling a negative = one uniform integer into the
pool — no alias tables on device).

Device training: the WHOLE run is one program — a ``lax.while_loop``
of minibatch SGNS steps over the pair list sharded across the mesh.
Each step gathers the batch's embedding rows, computes
``log σ(u_ctx·v_w) + Σ_neg log σ(−u_neg·v_w)`` gradients, scatter-adds
them back with ``.at[].add``, ``psum``s the dense embedding gradients
and steps by the GLOBAL-batch mean (device-count invariant; below
``_shard_vocab_threshold`` a dense psum per step beats bespoke sparse
collectives). ABOVE the threshold the in-RAM fit AND the
single-process streamed fit switch to ``_sgns_trainer_sharded``:
embedding tables shard over the mesh and
batch-sized payloads ride a ``ppermute`` ring, so per-step traffic is
independent of vocab. Spark trains hierarchical softmax on the JVM —
SGNS is the TPU-idiomatic equivalent and is documented as such, not
imitated.

The fitted model maps token-list documents to the MEAN of their word
vectors (the upstream convention) and offers ``find_synonyms`` via
cosine top-k (one gemm + top_k).
"""

from __future__ import annotations

import functools
import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from flinkml_tpu.api import Estimator, Model
from flinkml_tpu.models._streaming import StreamingEstimatorMixin
from flinkml_tpu.common_params import (
    HasInputCol,
    HasLearningRate,
    HasMaxIter,
    HasOutputCol,
    HasSeed,
)
from flinkml_tpu.models.text import _token_column
from flinkml_tpu.params import FloatParam, IntParam, ParamValidators
from flinkml_tpu.parallel import DeviceMesh, pad_to_multiple
from flinkml_tpu.table import Table

_NEG_POOL = 1 << 18   # negative-sampling pool entries


class _Word2VecParams(HasInputCol, HasOutputCol, HasMaxIter,
                      HasLearningRate, HasSeed):
    VECTOR_SIZE = IntParam(
        "vectorSize", "Embedding dimensionality.", 100, ParamValidators.gt(0)
    )
    WINDOW_SIZE = IntParam(
        "windowSize", "Max distance between center and context.", 5,
        ParamValidators.gt(0),
    )
    MIN_COUNT = IntParam(
        "minCount", "Tokens rarer than this are dropped.", 5,
        ParamValidators.gt(0),
    )
    NUM_NEGATIVES = IntParam(
        "numNegatives", "Negative samples per (center, context) pair.", 5,
        ParamValidators.gt(0),
    )
    BATCH_SIZE = IntParam(
        "batchSize", "Global pairs per SGNS step.", 1024,
        ParamValidators.gt(0),
    )
    MAX_STEPS = IntParam(
        "maxSteps", "SGNS steps of a fit(Table); 0: maxIter epochs' steps "
        "(an epoch is the corpus's expected pairs / batchSize).", 0,
        ParamValidators.gt_eq(0),
    )
    SUBSAMPLE = FloatParam(
        "subsample", "word2vec.c's threshold t of fit(Table): an occurrence "
        "of a word of frequency f survives with probability "
        "min(1, sqrt(t / f) + t / f); 0: every occurrence.", 0.0,
        ParamValidators.gt_eq(0.0),
    )


def _agree_token_counts(tokens, counts, mesh) -> "Dict[str, int]":
    """Union the per-process (token, count) maps through the device
    fabric: each token rides as UTF-8 bytes (values 0-255 — exact on
    the f64 hi/lo transport of ``stream_sync.gather_vectors``) with its
    count, padded to the agreed (max tokens, max byte length); every
    host decodes the gathered rows in rank order and sums counts per
    token, so the merged map is identical everywhere. An empty local
    vocabulary is legal. Transport cost is
    ``P x max_tokens x (max_len + 2) x 8`` bytes through device memory
    — sized for real vocabularies (1e5 tokens x 32 bytes ≈ 27 MB/rank),
    not for unbounded cardinality."""
    from flinkml_tpu.iteration.stream_sync import agree_max, gather_vectors

    enc = [str(t).encode("utf-8") for t in tokens]
    t_max = agree_max(len(enc), mesh)
    if t_max == 0:
        return {}
    l_max = agree_max(max((len(b) for b in enc), default=0), mesh)
    stride = 2 + l_max
    vec = np.zeros(1 + t_max * stride)
    vec[0] = len(enc)
    for j, b in enumerate(enc):
        off = 1 + j * stride
        vec[off] = len(b)
        vec[off + 1] = counts[j]
        vec[off + 2 : off + 2 + len(b)] = np.frombuffer(b, np.uint8)
    rows = gather_vectors(vec, mesh)
    merged: Dict[str, int] = {}
    for row in rows:  # rank order: identical merge on every host
        for j in range(int(round(row[0]))):
            off = 1 + j * stride
            blen = int(round(row[off]))
            tok = (
                np.asarray(row[off + 2 : off + 2 + blen])
                .astype(np.uint8).tobytes().decode("utf-8")
            )
            merged[tok] = merged.get(tok, 0) + int(round(row[off + 1]))
    return merged


def _w2v_accum() -> str:
    """Embedding-gradient accumulation layout of the dense SGNS trainer
    (the roofline audit's sort-class gap: XLA lowers the per-step row
    scatters into ``[vocab, dim]`` through a sort, pinning the stage at
    ~5% of its ~40M pairs/s bound — VERDICT Missing #3, probed by
    ``tools/w2v_scatter_probe.py``). ``FLINKML_TPU_W2V_ACCUM`` selects,
    mirroring the GBT/ALS cumsum gates:

    - ``scatter`` (default): ``.at[ids].add(rows)`` — the original
      formulation;
    - ``onehot``: ``one_hot(ids)^T @ rows`` as a fused einsum — a true
      matrix-matrix product on the MXU IF XLA fuses the iota-compare
      into the dot operand (the probe's question; flip the default only
      on a measured win).

    Numerics: both accumulate the same per-pair gradients; they differ
    only in f32 summation order (pinned in ``tests/test_word2vec.py::
    test_onehot_accum_matches_scatter``)."""
    layout = os.environ.get("FLINKML_TPU_W2V_ACCUM")
    if layout is None:
        # Measured default for this mesh (autotune tuning table), else
        # the historical "scatter".
        from flinkml_tpu.autotune import tuned_default

        return tuned_default("w2v_accum", "scatter",
                             allowed=("scatter", "onehot"))
    if layout not in ("scatter", "onehot"):
        raise ValueError(
            f"FLINKML_TPU_W2V_ACCUM={layout!r}: expected 'scatter' or "
            "'onehot'"
        )
    return layout


#: The precision of the scores' products: float32-accurate on every
#: backend (a TPU's compiler turns these skinny products into float32
#: multiplies and sums of its own accord; where one goes to a matrix unit
#: it may not take one bfloat16 pass).
SCORE_PRECISION = jax.lax.Precision.HIGHEST


def _sgns_pair_grads(vc, uc, un, wb, score_dtype=None):
    """SGNS pair gradients from the gathered embedding rows — the ONE
    definition of the loss math, shared by the table fit, the dense and
    the vocab-sharded trainers (their numerics-parity contract,
    ``tests/test_word2vec.py::test_sharded_trainer_matches_one_device``,
    depends on it). Returns ``(grad_vc, grad_uc, grad_un)``.
    ``score_dtype`` is the benchmark's control's alone: ``bfloat16``
    rounds every product's operands as one bfloat16 pass would."""
    def operand(x):
        if score_dtype is None:
            return x
        info = jnp.finfo(score_dtype)
        return jax.lax.reduce_precision(x, info.nexp, info.nmant)

    vs, us, ns = operand(vc), operand(uc), operand(un)
    pos_score = jnp.sum(vs * us, axis=1)
    neg_score = jnp.einsum("bd,bnd->bn", vs, ns, precision=SCORE_PRECISION)
    g_pos = (jax.nn.sigmoid(pos_score) - 1.0) * wb   # [bs]
    g_neg = jax.nn.sigmoid(neg_score) * wb[:, None]  # [bs, neg]
    grad_vc = g_pos[:, None] * uc + jnp.einsum(
        "bn,bnd->bd", operand(g_neg), ns, precision=SCORE_PRECISION)
    grad_uc = g_pos[:, None] * vc
    grad_un = g_neg[..., None] * vc[:, None, :]
    return grad_vc, grad_uc, grad_un


def start_vectors(seed: int, vocab: int, dim: int) -> jax.Array:
    """A table fit's start word vectors, made on the device from the seed
    alone: uniform in ``[-0.5, 0.5) / dim``, float32 ``[vocab, dim]`` (the
    context vectors start at 0)."""
    return (jax.random.uniform(jax.random.PRNGKey(seed), (vocab, dim),
                               jnp.float32) - 0.5) / dim


@functools.lru_cache(maxsize=8)
def _sgns_trainer(mesh, axis: str, local_bs: int, n_neg: int,
                  accum: str = "scatter"):
    def local(centers, contexts, wl, pool, v0, u0, lr, n_steps, key):
        n_local = centers.shape[0]

        def onehot_sum(table_like, ids, rows):
            """``one_hot(ids)^T @ rows`` — the gated scatter-free
            accumulation (:func:`_w2v_accum`); ``ids`` may be [bs] or
            [bs, neg]."""
            flat_ids = ids.reshape(-1)
            flat_rows = rows.reshape(-1, rows.shape[-1])
            oh = jax.nn.one_hot(
                flat_ids, table_like.shape[0], dtype=flat_rows.dtype
            )
            return jnp.einsum("bv,bd->vd", oh, flat_rows)

        def body(state):
            step, v, u = state
            k = jax.random.fold_in(key, step)
            k1, k2 = jax.random.split(k)
            idx = jax.random.randint(k1, (local_bs,), 0, n_local)
            c = centers[idx]
            ctx = contexts[idx]
            wb = wl[idx]                   # [bs]; 0 on dummy chunks
            neg = pool[jax.random.randint(
                k2, (local_bs, n_neg), 0, pool.shape[0]
            )]
            vc = v[c]                      # [bs, d]
            uc = u[ctx]                    # [bs, d]
            un = u[neg]                    # [bs, neg, d]
            grad_vc, grad_uc, grad_un = _sgns_pair_grads(vc, uc, un, wb)
            if accum == "onehot":
                dv = onehot_sum(v, c, grad_vc)
                du = onehot_sum(u, ctx, grad_uc) + onehot_sum(
                    u, neg, grad_un
                )
            else:
                dv = jnp.zeros_like(v).at[c].add(grad_vc)
                du = (
                    jnp.zeros_like(u).at[ctx].add(grad_uc)
                    .at[neg.reshape(-1)].add(
                        grad_un.reshape(-1, grad_un.shape[-1])
                    )
                )
            # Device-invariant normalization: psum the per-device sums
            # and divide by the GLOBAL selected weight, so learningRate
            # means "step on the mean pair gradient" regardless of mesh
            # size (pmean of sums would shrink the step by the device
            # count). All-ones weights make this exactly the global
            # batch size (f32 sums of ones are exact at these sizes);
            # zero-weight rows (multi-process dummy chunks) drop out of
            # both the gradient and the normalizer.
            tw = jnp.maximum(jax.lax.psum(jnp.sum(wb), axis), 1e-12)
            scale = lr / tw
            dv = jax.lax.psum(dv, axis)
            du = jax.lax.psum(du, axis)
            return step + 1, v - scale * dv, u - scale * du

        def cond(state):
            return state[0] < n_steps

        _, v, u = jax.lax.while_loop(cond, body, (jnp.asarray(0, jnp.int32),
                                                  v0, u0))
        return v, u

    return jax.jit(
        jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(), P(), P(), P(), P(), P()),
            out_specs=(P(), P()),
        )
    )


@functools.lru_cache(maxsize=8)
def _sgns_trainer_sharded(mesh, axis: str, local_bs: int, n_neg: int,
                          shard_rows: int, strategy: str = "ring",
                          corpus=None, score_dtype=None):
    """Vocab-sharded SGNS trainer: the scale path above the embedding
    dense-psum threshold (VERDICT r4 weak #6 — the dense trainer psums
    a full ``[vocab, dim]`` gradient every step, quadratically painful
    at the 1M+ vocabs the Spark-family operator serves).

    Re-expressed on the :mod:`flinkml_tpu.embeddings.exchange`
    primitives (this trainer is where they were born — the ring loops
    moved there verbatim, so the ``ring`` strategy is bit-identical to
    the pre-subsystem trainer): both embedding tables shard over the
    mesh axis (``shard_rows`` rows per device); per-step communication
    is the BATCH's activation and gradient rows riding the
    strategy-gated exchange, never a vocab-sized array:

      1. ONE exchange gather — each device's minibatch ids for BOTH
         tables (center ids against v; context + negative ids against
         u) resolve to complete rows (``ppermute`` ring hops, or one
         ``all_to_all`` under the gated strategy).
      2. local pair math — :func:`_sgns_pair_grads`, shared with the
         dense trainer.
      3. ONE exchange scatter — the scaled gradient rows for both
         tables route home.

    Per step, per device: ``2·(2 + n_neg)·global_bs·dim`` floats total
    regardless of strategy — independent of vocab AND of P. Numerics
    match the dense trainer up to f32 summation order; the strategies
    match each other bitwise on the gather and up to summation order on
    the scatter (both pinned in ``tests/test_word2vec.py`` /
    ``tests/test_embeddings.py``).

    The streamed fit hands it a chunk of the pair list, ``(centers,
    contexts, weights, pool, v, u, lr, steps, key)``. A table fit names
    its ``corpus`` (a :class:`_w2v_table.Draw`) and calls ``(v, u, tokens,
    keep, pool, seed, lr, steps)``: the corpus is replicated, every
    device makes the step's whole draw (:func:`_w2v_table.draw`) and takes
    its ``local_bs`` slots of it, so the step is the one-device program's
    on the same pairs."""
    from flinkml_tpu.embeddings import exchange

    p = dict(mesh.shape)[axis]

    def loop(sample, v_shard, u_shard, lr, n_steps):
        def body(state):
            step, v, u = state
            c, ctx, wb, neg = sample(step)
            vc, uc, un = exchange.gather(
                ((v, c), (u, ctx), (u, neg)),
                axes=axis, n_shards=p, shard_rows=shard_rows,
                strategy=strategy,
            )
            grad_vc, grad_uc, grad_un = _sgns_pair_grads(
                vc, uc, un, wb, score_dtype=score_dtype)
            tw = jnp.maximum(jax.lax.psum(jnp.sum(wb), axis), 1e-12)
            scale = lr / tw
            v, u = exchange.scatter_add(
                (v, u),
                (
                    (0, c, -scale * grad_vc),
                    (1, ctx, -scale * grad_uc),
                    (1, neg, -scale * grad_un),
                ),
                axes=axis, n_shards=p, shard_rows=shard_rows,
                strategy=strategy)
            return step + 1, v, u

        def cond(state):
            return state[0] < n_steps

        _, v, u = jax.lax.while_loop(
            cond, body, (jnp.asarray(0, jnp.int32), v_shard, u_shard)
        )
        return v, u

    def local(centers, contexts, wl, pool, v_shard, u_shard, lr, n_steps,
              key):
        n_local = centers.shape[0]

        def sample(step):
            k = jax.random.fold_in(key, step)
            k1, k2 = jax.random.split(k)
            idx = jax.random.randint(k1, (local_bs,), 0, n_local)
            neg = pool[jax.random.randint(
                k2, (local_bs, n_neg), 0, pool.shape[0]
            )]
            return centers[idx], contexts[idx], wl[idx], neg

        return loop(sample, v_shard, u_shard, lr, n_steps)

    def local_corpus(v_shard, u_shard, tokens, keep, pool, seed, lr, n_steps):
        from flinkml_tpu.models import _w2v_table

        def sample(step):
            c, ctx, neg, found = _w2v_table.draw(
                corpus, tokens, keep, pool, seed, step.astype(jnp.uint32))
            mine = jax.lax.axis_index(axis) * local_bs
            c, ctx, neg = (jax.lax.dynamic_slice_in_dim(x, mine, local_bs)
                           for x in (c, ctx, neg))
            wb = jnp.full(local_bs, found > 0, v_shard.dtype)
            return c, ctx, wb, neg

        return loop(sample, v_shard, u_shard, lr, n_steps)

    if corpus is not None:
        return jax.jit(jax.shard_map(
            local_corpus, mesh=mesh,
            in_specs=(P(axis), P(axis)) + (P(),) * 6,
            out_specs=(P(axis), P(axis)),
        ))
    return jax.jit(
        jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(), P(axis), P(axis),
                      P(), P(), P()),
            out_specs=(P(axis), P(axis)),
        )
    )


def _shard_vocab_threshold() -> int:
    """Vocab size above which the in-RAM fit switches to the
    vocab-sharded exchange trainer on a multi-device mesh (the dense
    trainer's per-step [vocab, dim] gradient psum stops scaling there).
    Now the embedding subsystem's ONE dense-psum threshold
    (:func:`flinkml_tpu.embeddings.dense_vocab_threshold`;
    ``FLINKML_TPU_EMBEDDING_DENSE_VOCAB`` overrides it, 0 forces
    sharding — the test hook)."""
    from flinkml_tpu.embeddings import dense_vocab_threshold

    return dense_vocab_threshold()


def _exchange_strategy() -> str:
    """The sharded exchange algorithm for this fit — resolved once at
    fit time (env > autotune ``embedding_exchange`` > ring) and threaded
    through the trainer's lru key, mirroring :func:`_w2v_accum`."""
    from flinkml_tpu.embeddings import exchange_strategy

    return exchange_strategy()


class Word2Vec(StreamingEstimatorMixin, _Word2VecParams, Estimator):
    """``fit`` accepts, besides a single in-RAM :class:`Table`, an
    **iterable of batch Tables** — the out-of-core path: pass A encodes
    the token stream to an int-coded doc cache (strings never spill; the
    vocabulary dictionary is model-sized host state), pass B replays it
    into a (center, context) pair cache, and each training epoch replays
    the pair cache chunk-by-chunk — SGNS minibatches sample within the
    resident chunk, the classic word2vec sequential-corpus discipline
    (reference replay parity: ``ReplayOperator.java:62-250``).
    ``checkpoint_manager`` + ``checkpoint_interval`` snapshot both
    embedding matrices every N epochs; ``resume=True`` continues
    bit-exactly PROVIDED the caller re-feeds the complete identical
    stream — Word2Vec cannot take a sealed DataCache (no string
    vocabulary), so the durable-input guard the other streamed fits
    enforce cannot apply here; passes A/B re-run deterministically from
    the same seed over the re-fed stream."""


    def fit(self, *inputs) -> "Word2VecModel":
        (table,) = inputs
        if not isinstance(table, Table):
            return self._fit_stream(table)
        self._reject_in_ram_checkpointing()
        from flinkml_tpu.models import _w2v_table
        from flinkml_tpu.utils.profiling import span

        with span("fit"):
            vocab, vectors = _w2v_table.fit_table(self, table)
            model = Word2VecModel()
            model.copy_params_from(self)
            model._set(vocab, vectors)
        return model

    # Pair-chunk row tile: bounds the set of padded chunk shapes (and so
    # trainer recompiles) while keeping chunks MXU-sized.
    _PAIR_TILE = 2048

    def _fit_stream(self, source) -> "Word2VecModel":
        """Out-of-core SGNS (see class docstring).

        Multi-process (round 4): each process feeds its OWN document
        partition. The string vocabulary unions through the device
        fabric — tokens ride as UTF-8 bytes on the f64-exact transport
        (:func:`_agree_token_counts`) — so every rank holds the
        identical (token, count) map; pair building then stays
        rank-local (per-rank deterministic window RNG), and each
        training dispatch is one agreed-step SGNS run over every rank's
        resident chunk with psum'd gradients (drained ranks feed
        zero-weight dummy chunks). The negative pool and embedding init
        draw from a fresh seed-only RNG so they are identical on every
        rank; the fitted vectors are identical on every rank."""
        import os
        import shutil
        import tempfile

        from flinkml_tpu.iteration.checkpoint import (
            begin_resume,
            should_snapshot,
        )
        from flinkml_tpu.iteration.datacache import (
            DataCache,
            DataCacheWriter,
        )

        if isinstance(source, DataCache):
            raise ValueError(
                "Word2Vec streamed fit takes an iterable of batch Tables "
                "(token documents are encoded internally; a raw DataCache "
                "carries no string vocabulary)"
            )
        multi = jax.process_count() > 1
        input_col = self.get(self.INPUT_COL)
        min_count = self.get(self.MIN_COUNT)
        window = self.get(self.WINDOW_SIZE)
        mesh = self.mesh or DeviceMesh()
        p = mesh.axis_size()
        resume_epoch = begin_resume(
            self.checkpoint_manager, self.resume, mesh.mesh.size
        )

        # -- pass A: count tokens + cache int-coded docs -------------------
        # The doc cache is transient (consumed once by pass B), so it
        # lives in a private temp dir; the pair cache — replayed every
        # epoch — goes to the user's cache_dir.
        if self.cache_dir is not None:
            os.makedirs(self.cache_dir, exist_ok=True)
        doc_dir = tempfile.mkdtemp(prefix="flinkml-w2v-docs-",
                                   dir=self.cache_dir)
        pid: Dict[str, int] = {}
        counts_list: List[int] = []
        try:
            doc_writer = DataCacheWriter(
                doc_dir, self.cache_memory_budget_bytes
            )

            def ingest_docs(t):
                docs = _token_column(t, input_col)
                codes: List[int] = []
                lengths: List[int] = []
                for toks in docs:
                    start = len(codes)
                    for tok in map(str, toks):
                        i = pid.get(tok)
                        if i is None:
                            i = pid[tok] = len(counts_list)
                            counts_list.append(0)
                        counts_list[i] += 1
                        codes.append(i)
                    lengths.append(len(codes) - start)
                if lengths:
                    # Flat single-column record (columns of a cached batch
                    # must agree on row count): [n_docs, *lengths, *codes].
                    doc_writer.append({
                        "rec": np.concatenate([
                            [len(lengths)], lengths, codes
                        ]).astype(np.int32),
                    })

            from flinkml_tpu.iteration.stream_sync import (
                DeferredValidation,
                checked_ingest,
            )

            dv = DeferredValidation()
            for _ in checked_ingest(source, dv, ingest_docs, multi):
                pass
            doc_cache = doc_writer.finish()

            tokens = np.empty(len(pid), dtype=object)
            for tok, i in pid.items():
                tokens[i] = tok
            if multi:
                # Rendezvous BEFORE the vocab union: a held ingest error
                # must surface as itself on every rank.
                dv.rendezvous(mesh, "stream ingest validation")
                merged = _agree_token_counts(
                    list(tokens), counts_list, mesh
                )
                if not merged:
                    raise ValueError(
                        "training stream is empty on every process"
                    )
                vocab = [t for t, c in merged.items() if c >= min_count]
                vocab.sort(key=lambda t: (-merged[t], t))
                if not vocab:  # merged is identical: symmetric raise
                    raise ValueError(
                        f"no token reaches minCount={min_count}; "
                        "vocabulary is empty"
                    )
                final_of_token = {t: f for f, t in enumerate(vocab)}
                final_of_pid = np.full(len(counts_list), -1, np.int32)
                for i in range(len(counts_list)):
                    final_of_pid[i] = final_of_token.get(str(tokens[i]), -1)
                vocab_counts = np.asarray(
                    [merged[t] for t in vocab], np.int64
                )
            else:
                counts_arr = np.asarray(counts_list, np.int64)
                kept = [i for i in range(len(counts_list))
                        if counts_arr[i] >= min_count]
                kept.sort(key=lambda i: (-counts_arr[i], tokens[i]))
                if not kept:
                    raise ValueError(
                        f"no token reaches minCount={min_count}; vocabulary "
                        "is empty"
                    )
                vocab = [tokens[i] for i in kept]
                final_of_pid = np.full(len(counts_list), -1, np.int32)
                for f, i in enumerate(kept):
                    final_of_pid[i] = f
                vocab_counts = counts_arr[kept]

            # Scale guard BEFORE pass B: the vocabulary is final here,
            # and failing now costs seconds — after pass B it would cost
            # a full doc-cache replay and a pair cache on disk first.
            # Single-process multi-device streams switch to the
            # vocab-sharded ring trainer below instead; only the
            # multi-PROCESS stream (whose per-rank pair partitions the
            # ring trainer does not yet route) rejects.
            if multi and len(vocab) > _shard_vocab_threshold():
                raise ValueError(
                    f"multi-process streamed Word2Vec fit: vocabulary "
                    f"({len(vocab)} tokens) exceeds the dense-gradient "
                    f"scale ceiling ({_shard_vocab_threshold()}): every "
                    "SGNS step would psum a full [vocab, dim] gradient "
                    "across processes. Use the in-RAM fit or a "
                    "single-process mesh (both switch to the "
                    "vocab-sharded ring trainer above this threshold), "
                    "raise minCount to prune the vocabulary, or override "
                    "via FLINKML_TPU_EMBEDDING_DENSE_VOCAB."
                )

            # -- pass B: replay doc cache into the pair cache --------------
            # Multi-process: per-rank deterministic window RNG (pairs are
            # rank-local); the pool/init RNG below is then seed-only so
            # those draws are identical on every rank.
            if multi:
                rng = np.random.default_rng(
                    [self.get_seed(), 1 + jax.process_index()]
                )
            else:
                rng = np.random.default_rng(self.get_seed())
            pair_writer = DataCacheWriter(
                self.cache_dir, self.cache_memory_budget_bytes
            )
            n_pairs = 0
            for batch in doc_cache.reader():
                rec = batch["rec"]
                n_docs = int(rec[0])
                lengths_b = rec[1:1 + n_docs]
                fids = final_of_pid[rec[1 + n_docs:]]
                centers: List[int] = []
                contexts: List[int] = []
                off = 0
                for length in lengths_b:
                    ids = [int(c) for c in fids[off:off + length] if c >= 0]
                    off += int(length)
                    for i, c in enumerate(ids):
                        w = int(rng.integers(1, window + 1))
                        for j in range(max(0, i - w),
                                       min(len(ids), i + w + 1)):
                            if j != i:
                                centers.append(c)
                                contexts.append(ids[j])
                if centers:
                    pair_writer.append({
                        "c": np.asarray(centers, np.int32),
                        "x": np.asarray(contexts, np.int32),
                    })
                    n_pairs += len(centers)
            pair_cache = pair_writer.finish()
        finally:
            shutil.rmtree(doc_dir, ignore_errors=True)
        if multi:
            from flinkml_tpu.iteration.stream_sync import gather_vectors

            total_pairs = int(round(gather_vectors(
                np.asarray([float(n_pairs)]), mesh
            ).sum()))
            if total_pairs == 0:
                raise ValueError(
                    "no (center, context) pairs on any process; documents "
                    "too short"
                )
        elif n_pairs == 0:
            raise ValueError("no (center, context) pairs; documents too short")

        # unigram^0.75 negative pool over the FINAL vocab (seed-only RNG
        # under multi-process — identical pool/init on every rank).
        rng_global = (
            np.random.default_rng(self.get_seed()) if multi else rng
        )
        freq = vocab_counts.astype(np.float64) ** 0.75
        pool = rng_global.choice(
            len(vocab), size=_NEG_POOL, p=freq / freq.sum()
        ).astype(np.int32)
        pool_dev = jnp.asarray(pool)

        dim = self.get(self.VECTOR_SIZE)
        batch_size = self.get(self.BATCH_SIZE)
        local_bs = max(1, batch_size // p)
        # Above the vocab threshold on a single-process multi-device
        # mesh, the streamed fit uses the same vocab-sharded ring
        # trainer as the in-RAM fit (the multi-PROCESS case was
        # rejected with guidance right after the vocabulary was final).
        use_sharded = p > 1 and len(vocab) > _shard_vocab_threshold()
        if use_sharded:
            shard_rows = -(-len(vocab) // p)
            vocab_pad = shard_rows * p
            trainer = _sgns_trainer_sharded(
                mesh.mesh, DeviceMesh.DATA_AXIS, local_bs,
                self.get(self.NUM_NEGATIVES), shard_rows,
                _exchange_strategy())
        else:
            trainer = _sgns_trainer(
                mesh.mesh, DeviceMesh.DATA_AXIS, local_bs,
                self.get(self.NUM_NEGATIVES), _w2v_accum())
        lr = jnp.asarray(self.get(self.LEARNING_RATE), jnp.float32)
        base_key = jax.random.PRNGKey(self.get_seed())
        tile = p * self._PAIR_TILE

        def place_vu(v_h, u_h):
            """Device placement of the embedding pair: replicated for the
            dense trainer, row-sharded (padded) for the ring trainer."""
            if not use_sharded:
                return jnp.asarray(v_h), jnp.asarray(u_h)
            pad = vocab_pad - len(vocab)
            z = np.zeros((pad, dim), np.float32)
            return (
                mesh.shard_batch(np.concatenate([v_h, z])),
                mesh.shard_batch(np.concatenate([u_h, z])),
            )

        u_h0 = np.zeros((len(vocab), dim), np.float32)
        start_epoch = 0
        if resume_epoch is None:
            v_h0 = (
                (rng_global.random((len(vocab), dim)) - 0.5)
                .astype(np.float32) / dim
            )
        else:
            like = (np.zeros((len(vocab), dim), np.float32),) * 2
            from flinkml_tpu.iteration.stream_sync import agreed_restore

            (v_h0, u_h0), start_epoch = agreed_restore(
                self.checkpoint_manager, resume_epoch, like, mesh
            )
        v, u = place_vu(v_h0, u_h0)

        from flinkml_tpu.parallel.dispatch import DispatchGuard

        guard = DispatchGuard()  # multi-process backpressure (no-op single)
        local_tile = (p // jax.process_count()) * self._PAIR_TILE
        max_iter = self.get(self.MAX_ITER)
        for epoch in range(start_epoch, max_iter):
            if multi:
                from flinkml_tpu.iteration.stream_sync import (
                    agree_max,
                    synced_stream,
                )

                # Data-proportional training intensity: distribute the
                # single-process per-epoch step budget (global pairs /
                # batch_size) evenly over the agreed dispatch count, so
                # dummy padding on skewed or drained ranks never
                # inflates the SGD step count over the real pairs.
                n_dispatch = max(1, agree_max(pair_cache.num_batches, mesh))
                steps = max(1, total_pairs // (batch_size * n_dispatch))
                # Agreed per-dispatch height (tiles ride the step
                # agreement), so every rank runs the same collectives;
                # drained ranks feed zero-weight dummy chunks.
                height_of = lambda b: -(-max(len(b["c"]), 1) // local_tile)
                for ci, (b, tiles) in enumerate(synced_stream(
                    pair_cache.reader(), mesh, payload=height_of
                )):
                    h = tiles * local_tile
                    if b is None:
                        c_p = np.zeros(h, np.int32)
                        x_p = np.zeros(h, np.int32)
                        w_p = np.zeros(h, np.float32)
                    else:
                        # Pad by CYCLING real pairs (a zero pad would be
                        # a genuine (0, 0) positive pair).
                        c_p, x_p = np.resize(b["c"], h), np.resize(b["x"], h)
                        w_p = np.ones(h, np.float32)
                    v, u = trainer(
                        mesh.global_batch(c_p), mesh.global_batch(x_p),
                        mesh.global_batch(w_p),
                        pool_dev, v, u, lr,
                        jnp.asarray(steps, jnp.int32),
                        jax.random.fold_in(
                            jax.random.fold_in(base_key, epoch), ci
                        ),
                    )
                    guard.after_dispatch(v)
            else:
                for ci, batch in enumerate(pair_cache.reader()):
                    c, x = batch["c"], batch["x"]
                    rows = max(tile, -(-len(c) // tile) * tile)
                    # Pad by CYCLING real pairs (a zero pad would be a
                    # genuine (0, 0) positive pair — see the in-RAM
                    # path's rationale).
                    c_p, x_p = np.resize(c, rows), np.resize(x, rows)
                    steps = max(1, len(c) // batch_size)
                    v, u = trainer(
                        mesh.shard_batch(c_p), mesh.shard_batch(x_p),
                        mesh.shard_batch(np.ones(rows, np.float32)),
                        pool_dev, v, u, lr, jnp.asarray(steps, jnp.int32),
                        jax.random.fold_in(
                            jax.random.fold_in(base_key, epoch), ci
                        ),
                    )
            if should_snapshot(self.checkpoint_manager,
                               self.checkpoint_interval, epoch + 1,
                               max_iter):
                # Slice off the shard padding rows (no-op unsharded) so
                # checkpoints are layout-independent.
                state = (
                    np.asarray(v)[: len(vocab)],
                    np.asarray(u)[: len(vocab)],
                )
                if multi:
                    from flinkml_tpu.iteration.checkpoint import (
                        save_replicated,
                    )

                    save_replicated(
                        self.checkpoint_manager, state, epoch + 1, mesh
                    )
                else:
                    self.checkpoint_manager.save(state, epoch + 1)
        guard.flush(v)

        model = Word2VecModel()
        model.copy_params_from(self)
        model._set(
            np.asarray(vocab, dtype=str), np.asarray(v)[: len(vocab)],
        )
        return model


class Word2VecModel(_Word2VecParams, Model):
    """The fitted vectors as the chip returned them (float32, ``[vocab,
    dim]``: :meth:`word_vectors`); :attr:`vectors` widens them to float64
    when asked, once. The word index is built at its first use."""

    def __init__(self):
        super().__init__()
        self._vocab: Optional[np.ndarray] = None
        self._vectors: Optional[np.ndarray] = None

    def _set(self, vocab: np.ndarray, vectors: np.ndarray) -> None:
        self._vocab = vocab
        self._vectors = vectors
        for cached in ("_index", "vectors"):
            self.__dict__.pop(cached, None)

    @functools.cached_property
    def _index(self) -> Dict[str, int]:
        return {str(t): i for i, t in enumerate(self._vocab)}

    @property
    def vocabulary(self) -> np.ndarray:
        self._require()
        return self._vocab

    @functools.cached_property
    def vectors(self) -> np.ndarray:
        self._require()
        return np.asarray(self._vectors, np.float64)

    def word_vectors(self) -> np.ndarray:
        """The vectors as held (a fit's: the chip's float32), no copy."""
        self._require()
        return self._vectors

    def set_model_data(self, *inputs: Table) -> "Word2VecModel":
        (table,) = inputs
        self._set(
            np.asarray(table.column("word"), dtype=str),
            np.asarray(table.column("vector")),
        )
        return self

    def get_model_data(self) -> List[Table]:
        self._require()
        return [Table({"word": self._vocab, "vector": self._vectors})]

    def _require(self) -> None:
        if self._vocab is None:
            raise ValueError("Model data is not set; fit or set_model_data first")

    def transform(self, *inputs: Table) -> Tuple[Table, ...]:
        """Document vector = mean of its in-vocabulary word vectors
        (zero vector when none are in vocabulary) — the upstream layout."""
        (table,) = inputs
        self._require()
        docs = _token_column(table, self.get(self.INPUT_COL))
        dim = self._vectors.shape[1]
        out = np.zeros((len(docs), dim))
        for i, toks in enumerate(docs):
            ids = [self._index[t] for t in map(str, toks) if t in self._index]
            if ids:
                out[i] = self._vectors[ids].mean(axis=0, dtype=np.float64)
        return (table.with_column(self.get(self.OUTPUT_COL), out),)

    def find_synonyms(self, word: str, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Top-k cosine-similar vocabulary words (one gemm + top_k)."""
        self._require()
        i = self._index.get(str(word))
        if i is None:
            raise ValueError(f"word {word!r} is not in the vocabulary")
        vecs = jnp.asarray(self._vectors, jnp.float32)
        norms = jnp.linalg.norm(vecs, axis=1) + 1e-12
        sims = (vecs @ vecs[i]) / (norms * norms[i])
        sims = sims.at[i].set(-jnp.inf)      # exclude the word itself
        vals, idx = jax.lax.top_k(sims, min(k, len(self._vocab) - 1))
        return self._vocab[np.asarray(idx)], np.asarray(vals)

    def save(self, path: str) -> None:
        self._require()
        self._save_with_arrays(
            path, {"word": self._vocab, "vector": self._vectors}
        )

    @classmethod
    def load(cls, path: str) -> "Word2VecModel":
        model, arrays, _ = cls._load_with_arrays(path)
        model._set(arrays["word"].astype(str), arrays["vector"])
        return model
