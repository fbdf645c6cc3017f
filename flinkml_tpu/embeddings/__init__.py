"""flinkml_tpu.embeddings — sharded embedding tables as a first-class
subsystem (ROADMAP item 1, the recommendation-scale carrier).

Every recsys-shaped member of the library (ALS, Swing, FM, Word2Vec,
LSH) stores a ``[vocab, dim]`` table; until this subsystem each was
capped by single-chip HBM — the dense trainers psum a vocab-sized
gradient per step, and the one scale path (Word2Vec's vocab-sharded
ring trainer) was welded to that one model. This package generalizes it
into a reusable primitive, exactly SNIPPETS.md [1]'s ``embeddings()``
spec (tables sharded ``PS((fsdp, tp), None)``):

- :class:`~flinkml_tpu.embeddings.table.EmbeddingTable` — rows sharded
  over the plan's ``(fsdp, tp)`` axes via the ``EMBEDDING``
  :class:`~flinkml_tpu.sharding.plan.ShardingPlan` family; optimizer
  slots shard identically; checkpoints ride plan-derived ``sharded:0``
  layout tags so world-N snapshots resume at world M.
- :mod:`~flinkml_tpu.embeddings.exchange` — the device-side sparse
  lookup (masked gather on the owning shard) and gradient exchange
  (batch-sized row payloads over ``ppermute`` rings or one
  ``all_to_all``, the scatter one ``segment_sum`` a table) — never a
  vocab-sized dense psum, never a host gather. Strategy is the ``embedding_exchange`` autotune knob;
  the ``dense_psum`` placement below the vocab threshold subsumes
  W2V's old static ``_shard_vocab_threshold``.
- :mod:`~flinkml_tpu.embeddings.serving` — a mesh-bindable lookup model
  serving a sharded table through the ReplicaPool's slice meshes with
  bf16 compute under ``PrecisionPolicy("mixed_inference")``.

Consumers: Word2Vec's sharded SGNS trainer is re-expressed on the
exchange primitives (pinned parity vs its dense twin), the FM trainers
shard their factor matrix + Adam slots through the plan's embedding
family, and ALS exports its factors as tables for sharded serving while
refusing loudly to train sharded (its normal-equation buffers are
vocab-sized — the primitive does not remove that wall).

See ``docs/development/embeddings.md`` for the layout contract, the
exchange algorithms, the checkpoint tag format, the serving path, and
the tuning knobs.
"""

from flinkml_tpu.embeddings.exchange import (  # noqa: F401
    ENV_DENSE_VOCAB_VAR,
    ENV_VAR,
    STRATEGIES,
    dense_vocab_threshold,
    exchange_strategy,
    resolve_exchange,
    shard_rows_for,
)
from flinkml_tpu.embeddings.table import EmbeddingTable  # noqa: F401

__all__ = [
    "ENV_DENSE_VOCAB_VAR",
    "ENV_VAR",
    "STRATEGIES",
    "EmbeddingTable",
    "dense_vocab_threshold",
    "exchange_strategy",
    "resolve_exchange",
    "shard_rows_for",
]
