"""Sparse lookup + gradient exchange over sharded embedding rows.

The device-side half of :mod:`flinkml_tpu.embeddings`: a family of
shard-level primitives, called INSIDE ``shard_map``, that move **batch-
sized row payloads** between the shards of a row-sharded ``[vocab, dim]``
table — never a vocab-sized dense array and never a host gather. They
generalize the Word2Vec vocab-sharded ring trainer's masked-gather /
masked-scatter loops (``flinkml_tpu/models/word2vec.py``, PR "scale
path") from one hard-coded ``data`` axis to ANY composite axis tuple a
:class:`~flinkml_tpu.sharding.plan.ShardingPlan` names — the ``EMBEDDING``
family's ``(fsdp, tp)`` product included (``ppermute``/``psum``/
``all_to_all`` all accept composite axis names; verified against this
repo's jax pin).

Ownership contract (shared by every strategy): shard ``r`` (the
flattened ``axis_index`` over ``axes``) owns global rows
``[r·shard_rows, (r+1)·shard_rows)`` of the padded table. A gather sums
per-shard contributions that are zero everywhere except the one owning
shard, so **lookups are exact** — bitwise identical across strategies
AND across world sizes (adding f32 zeros is exact). Scatter-adds differ
between strategies only in f32 summation order on duplicate ids, the
same contract the W2V ring trainer already pins against its dense twin.

Three strategies (the ``embedding_exchange`` autotune knob family):

- ``ring`` — ids + row accumulators ride ``ppermute`` hops; every
  visited shard adds the rows it owns. P hops of ``batch × dim``
  payload; the W2V formulation, lifted verbatim.
- ``all_to_all`` — ids ``all_gather`` to every shard (cheap ints), each
  shard produces its masked contribution for the full global id list,
  and ONE ``all_to_all`` routes contributions home (gather) or the
  gathered rows ``segment_sum`` into the local shard (scatter). Same
  total traffic as the ring, 2 collectives instead of 2·P hops — the
  latency bet the device re-tune decides.
- ``dense_psum`` — not an exchange at all: the below-threshold
  placement where the table stays replicated and gradients ride one
  dense ``[vocab, dim]`` psum per step (the classic W2V dense trainer).
  :func:`resolve_exchange` routes small vocabs here, subsuming W2V's
  static ``_shard_vocab_threshold``; above the threshold it is refused
  (a vocab-sized psum is exactly what the subsystem exists to avoid).

Resolution precedence at every consumer (the repo's layout-gate idiom):
explicit ``FLINKML_TPU_EMBEDDING_EXCHANGE`` env var > the autotune
table's measured ``embedding_exchange`` winner for this mesh > the
static ``ring`` default. Consumers thread the resolved strategy through
their trainer factories' ``lru_cache`` keys, so a gate flip re-keys the
jitted program instead of silently reusing the old one.
"""

from __future__ import annotations

import os
from typing import Sequence, Tuple, Union

Axes = Union[str, Tuple[str, ...]]

#: The exchange strategies (and the autotune knob's candidate set).
STRATEGIES = ("ring", "all_to_all", "dense_psum")

#: Explicit strategy override (highest precedence).
ENV_VAR = "FLINKML_TPU_EMBEDDING_EXCHANGE"

#: Vocab-size override for the dense-psum threshold (lowest vocab that
#: SHARDS; 0 forces sharding — the test hook).
ENV_DENSE_VOCAB_VAR = "FLINKML_TPU_EMBEDDING_DENSE_VOCAB"

#: Below this vocab size a dense [vocab, dim] gradient psum per step
#: beats bespoke sparse collectives (the W2V measurement that set the
#: original ``_shard_vocab_threshold``).
DENSE_VOCAB_DEFAULT = 1 << 18


def dense_vocab_threshold() -> int:
    """The vocab size at or below which tables stay replicated and
    gradients ride a dense psum (the ``dense_psum`` placement)."""
    raw = os.environ.get(ENV_DENSE_VOCAB_VAR)
    return DENSE_VOCAB_DEFAULT if raw is None else int(raw)


def exchange_strategy() -> str:
    """The SHARDED exchange algorithm (``ring`` or ``all_to_all``):
    env var > autotune table > static ``ring``.

    ``dense_psum`` is a PLACEMENT (replicated table), not a sharded
    algorithm, so the two sources treat it differently: an EXPLICIT
    ``FLINKML_TPU_EMBEDDING_EXCHANGE=dense_psum`` on a sharded table is
    refused loudly (the gate idiom — an explicit request must never be
    silently rewritten; raise the dense-vocab threshold instead to
    force the dense placement), while a table-COMMITTED ``dense_psum``
    winner quietly falls back to ``ring`` (the knob's measurement size
    says nothing about an over-threshold table, which cannot ride a
    vocab-sized psum)."""
    raw = os.environ.get(ENV_VAR)
    if raw is not None:
        if raw not in STRATEGIES:
            raise ValueError(
                f"{ENV_VAR}={raw!r}: expected one of {STRATEGIES}"
            )
        if raw == "dense_psum":
            raise ValueError(
                f"{ENV_VAR}=dense_psum: dense_psum is the replicated "
                "PLACEMENT, not a sharded exchange algorithm — to force "
                f"the dense path, raise the vocab threshold instead "
                f"({ENV_DENSE_VOCAB_VAR}); on an already-sharded table "
                "pick 'ring' or 'all_to_all'"
            )
        return raw
    from flinkml_tpu.autotune import tuned_default

    chosen = tuned_default("embedding_exchange", "ring",
                           allowed=STRATEGIES)
    return chosen if chosen in ("ring", "all_to_all") else "ring"


def resolve_exchange(vocab: int, n_shards: int) -> str:
    """The strategy for a ``vocab``-row table over ``n_shards`` shards —
    the ONE decision point subsuming W2V's static threshold:
    ``dense_psum`` (replicated table, dense gradient psum) when the
    table cannot shard (``n_shards == 1``) or is small enough that the
    dense psum measured faster; else the tuned sharded algorithm."""
    if n_shards <= 1 or vocab <= dense_vocab_threshold():
        return "dense_psum"
    return exchange_strategy()


def shard_rows_for(vocab: int, n_shards: int) -> int:
    """Rows per shard (ceil) — shard ``r`` owns
    ``[r·shard_rows, (r+1)·shard_rows)`` of the zero-padded table."""
    return -(-int(vocab) // int(n_shards))


# -- shard-level primitives (call INSIDE shard_map) -------------------------


def _vary(x, axes: Axes):
    """Mark ``x`` device-varying over ``axes`` if it is not already
    (replicated operands entering a ring/fori carry must be uniformly
    varying — the W2V ``vary`` idiom, composite-axis-ready)."""
    import jax

    want = (axes,) if isinstance(axes, str) else tuple(axes)
    vma = jax.typeof(x).vma
    if all(a in vma for a in want):
        return x
    return jax.lax.pcast(x, axes, to="varying")


def owned(ids, axes: Axes, shard_rows: int):
    """``(mask, safe local index)`` for the global ids THIS shard owns."""
    import jax
    import jax.numpy as jnp

    lo = jax.lax.axis_index(axes) * shard_rows
    local_idx = ids - lo
    mask = (local_idx >= 0) & (local_idx < shard_rows)
    return mask, jnp.clip(local_idx, 0, shard_rows - 1)


def ring_gather(pairs: Sequence, *, axes: Axes, n_shards: int,
                shard_rows: int):
    """Rows of the row-sharded tables for each ``(table_shard, ids)`` in
    ``pairs`` — ONE ``ppermute`` ring loop carries every payload (ring
    latency paid once, not per table). ``ids`` may be ``[bs]`` or
    ``[bs, n]``; returns one ``ids.shape + (dim,)`` array per pair."""
    import jax
    import jax.numpy as jnp

    ring = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    idss = tuple(_vary(ids, axes) for _, ids in pairs)
    accs = tuple(
        _vary(jnp.zeros(ids.shape + (t.shape[1],), t.dtype), axes)
        for (t, _), ids in zip(pairs, idss)
    )

    def hop(_, carry):
        idss_c, accs_c = carry
        out = []
        for (table, _), ids_c, acc_c in zip(pairs, idss_c, accs_c):
            mask, safe = owned(ids_c, axes, shard_rows)
            out.append(acc_c + jnp.where(mask[..., None], table[safe], 0.0))
        return (
            tuple(jax.lax.ppermute(i, axes, ring) for i in idss_c),
            tuple(jax.lax.ppermute(a, axes, ring) for a in out),
        )

    _, accs_out = jax.lax.fori_loop(0, n_shards, hop, (idss, accs))
    return accs_out  # n_shards hops: payloads are back home, complete


def ring_scatter_add(tables: Sequence, triples: Sequence, *, axes: Axes,
                     n_shards: int, shard_rows: int):
    """Scatter-add each ``(table_slot, ids, rows)`` in ``triples`` into
    ``tables`` (a tuple of row-sharded shards) via ONE ring loop for
    every payload; returns the updated tuple."""
    import jax
    import jax.numpy as jnp

    ring = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    idss = tuple(_vary(ids, axes) for _, ids, _ in triples)
    rowss = tuple(_vary(rows, axes) for _, _, rows in triples)

    def hop(_, carry):
        idss_c, rowss_c, tabs = carry
        tabs = list(tabs)
        for (slot, _, _), ids_c, rows_c in zip(triples, idss_c, rowss_c):
            mask, safe = owned(ids_c, axes, shard_rows)
            tabs[slot] = tabs[slot].at[safe.reshape(-1)].add(
                jnp.where(mask[..., None], rows_c, 0.0)
                .reshape(-1, rows_c.shape[-1])
            )
        return (
            tuple(jax.lax.ppermute(i, axes, ring) for i in idss_c),
            tuple(jax.lax.ppermute(x, axes, ring) for x in rowss_c),
            tuple(tabs),
        )

    _, _, tables = jax.lax.fori_loop(
        0, n_shards, hop, (idss, rowss, tuple(tables))
    )
    return tables


def _flat_sizes(idss) -> Tuple[int, ...]:
    sizes = []
    for ids in idss:
        m = 1
        for d in ids.shape:
            m *= int(d)
        sizes.append(m)
    return tuple(sizes)


def a2a_gather(pairs: Sequence, *, axes: Axes, n_shards: int,
               shard_rows: int):
    """The ``all_to_all`` gather: ids ``all_gather`` to every shard,
    each shard contributes its masked rows for the FULL global id list,
    one ``all_to_all`` routes contributions home, and the sum over
    source shards (exactly one non-zero each) completes the rows —
    bitwise equal to :func:`ring_gather`.

    Like the ring loop, every payload in ``pairs`` rides ONE collective
    round — the flattened id lists concatenate into one ``all_gather``
    and the per-table masked contributions into one ``all_to_all`` (the
    tables' dims must match, which the W2V/table consumers guarantee;
    mixed dims fall back to a round per payload). Latency is what the
    strategy competes on, so per-payload collectives would bias the
    device re-tune against it."""
    import jax
    import jax.numpy as jnp

    dims = sorted({int(t.shape[1]) for t, _ in pairs})
    if len(dims) > 1:
        out = []
        for pair in pairs:
            out.extend(a2a_gather((pair,), axes=axes, n_shards=n_shards,
                                  shard_rows=shard_rows))
        return tuple(out)
    dim = dims[0]
    ms = _flat_sizes([ids for _, ids in pairs])
    total = sum(ms)
    flat = jnp.concatenate(
        [_vary(ids.reshape(-1), axes) for _, ids in pairs]
    )                                                    # [M]
    idsg = jax.lax.all_gather(flat, axes, tiled=True)    # [P*M]
    per_src = idsg.reshape(n_shards, total)
    contribs = []
    offset = 0
    for (table, _), m in zip(pairs, ms):
        seg = per_src[:, offset:offset + m].reshape(-1)
        mask, safe = owned(seg, axes, shard_rows)
        contribs.append(
            jnp.where(mask[:, None], table[safe], 0.0)
            .reshape(n_shards, m, dim)
        )
        offset += m
    back = jax.lax.all_to_all(
        jnp.concatenate(contribs, axis=1),               # [P, M, dim]
        axes, split_axis=0, concat_axis=0, tiled=True,
    )
    rows = jnp.sum(back, axis=0)                         # [M, dim]
    out = []
    offset = 0
    for (_, ids), m in zip(pairs, ms):
        out.append(rows[offset:offset + m].reshape(ids.shape + (dim,)))
        offset += m
    return tuple(out)


def a2a_scatter_add(tables: Sequence, triples: Sequence, *, axes: Axes,
                    n_shards: int, shard_rows: int):
    """The ``all_to_all``-family scatter: every shard ``all_gather``s the
    (ids, rows) payloads and segment-sums the rows IT owns into its
    shard. Masked (non-owned) rows segment-sum as zeros into local row
    0 — the ELL no-op-add convention.

    All payloads ride ONE id ``all_gather`` + ONE row ``all_gather``
    (equal-dim payloads concatenate; mixed dims fall back to a round
    per payload) — the same latency discipline as :func:`a2a_gather`;
    the per-slot segment-sums stay separate, so the per-payload f32
    accumulation order is unchanged."""
    import jax
    import jax.numpy as jnp

    dims = sorted({int(rows.shape[-1]) for _, _, rows in triples})
    if len(dims) > 1:
        for triple in triples:
            tables = a2a_scatter_add(
                tables, (triple,), axes=axes, n_shards=n_shards,
                shard_rows=shard_rows)
        return tuple(tables)
    dim = dims[0]
    tables = list(tables)
    ms = _flat_sizes([ids for _, ids, _ in triples])
    total = sum(ms)
    flat_ids = jnp.concatenate(
        [_vary(ids.reshape(-1), axes) for _, ids, _ in triples]
    )
    flat_rows = jnp.concatenate(
        [_vary(rows.reshape(-1, dim), axes) for _, _, rows in triples]
    )
    idsg = jax.lax.all_gather(flat_ids, axes, tiled=True)    # [P*M]
    rowsg = jax.lax.all_gather(flat_rows, axes, tiled=True)  # [P*M, dim]
    per_src_ids = idsg.reshape(n_shards, total)
    per_src_rows = rowsg.reshape(n_shards, total, dim)
    offset = 0
    for (slot, _, _), m in zip(triples, ms):
        seg_ids = per_src_ids[:, offset:offset + m].reshape(-1)
        seg_rows = per_src_rows[:, offset:offset + m].reshape(-1, dim)
        mask, safe = owned(seg_ids, axes, shard_rows)
        tables[slot] = tables[slot] + jax.ops.segment_sum(
            jnp.where(mask[:, None], seg_rows, 0.0),
            jnp.where(mask, safe, 0),
            num_segments=shard_rows,
        )
        offset += m
    return tuple(tables)


def gather(pairs: Sequence, *, axes: Axes, n_shards: int, shard_rows: int,
           strategy: str = "ring"):
    """Strategy-dispatched sparse lookup (see the module docstring)."""
    if strategy == "ring":
        return ring_gather(pairs, axes=axes, n_shards=n_shards,
                           shard_rows=shard_rows)
    if strategy == "all_to_all":
        return a2a_gather(pairs, axes=axes, n_shards=n_shards,
                          shard_rows=shard_rows)
    raise ValueError(
        f"unknown sharded exchange strategy {strategy!r} (dense_psum is a "
        f"placement, not an exchange; expected 'ring' or 'all_to_all')"
    )


def scatter_add(tables: Sequence, triples: Sequence, *, axes: Axes,
                n_shards: int, shard_rows: int, strategy: str = "ring"):
    """Strategy-dispatched sparse gradient exchange (module docstring)."""
    if strategy == "ring":
        return ring_scatter_add(tables, triples, axes=axes,
                                n_shards=n_shards, shard_rows=shard_rows)
    if strategy == "all_to_all":
        return a2a_scatter_add(tables, triples, axes=axes,
                               n_shards=n_shards, shard_rows=shard_rows)
    raise ValueError(
        f"unknown sharded exchange strategy {strategy!r} (dense_psum is a "
        f"placement, not an exchange; expected 'ring' or 'all_to_all')"
    )


def psum_lookup(table_shard, ids, *, axes: Axes, shard_rows: int):
    """Replicated-ids lookup (the SERVING path): every shard gathers its
    masked contribution for the same global id list and one batch-sized
    ``psum`` completes the rows. Exactly one shard contributes per id,
    so the result is bitwise identical at every world size — what makes
    pool replicas and resharded resumes prediction-stable."""
    import jax
    import jax.numpy as jnp

    mask, safe = owned(ids, axes, shard_rows)
    contrib = jnp.where(mask[..., None], table_shard[safe], 0.0)
    return jax.lax.psum(contrib, axes)
