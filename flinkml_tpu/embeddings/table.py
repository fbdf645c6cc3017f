"""EmbeddingTable — a row-sharded ``[vocab, dim]`` parameter as a value.

The host-side half of :mod:`flinkml_tpu.embeddings`: one object that
owns the four decisions every 100M+-row table forces, each delegated to
the subsystem that already owns the mechanism:

- **layout** — rows shard over the plan's embedding axes (the
  ``EMBEDDING`` family's ``(fsdp, tp)`` product; any preset that keeps
  rows whole is legal). The plan is validated against the mesh by the
  FML5xx pass BEFORE any placement, with the table's padded shape and
  its optimizer slots counted (FML503's per-shard footprint branch), and
  ``plan=None`` routes through :func:`~flinkml_tpu.sharding.plan.
  infer_plan` — an over-budget vocab lands on the cheapest row-keeping
  plan or raises :class:`~flinkml_tpu.sharding.plan.NoFeasiblePlanError`.
- **access** — :meth:`lookup` (replicated ids, the serving path: one
  masked gather + batch-sized psum, bitwise stable at every world) and
  :meth:`scatter_add` (sharded batches, the training path: the
  strategy-gated exchange of :mod:`.exchange`).
- **optimizer state** — ``optimizer_slots`` same-shaped companions named
  ``<table>/embedding_slot<i>``, which land in the SAME plan family as
  the table (the ``*embedding*`` pattern matches both), so slots shard,
  checkpoint, and restore exactly like their parameter.
- **checkpointing** — :meth:`save` records the UNPADDED global array
  per leaf with plan-derived ``sharded:0`` layout tags
  (``CheckpointManager.save(..., plan=...)``), so a world-N snapshot
  restores at world M through the existing elastic machinery
  (:meth:`restore` re-pads and re-places for the new mesh; the restored
  host table is bit-equal to the saved one).

Naming contract: the table's parameter is ``<name>/embedding`` — the
``*embedding*`` family pattern (:data:`~flinkml_tpu.sharding.plan.
EMBEDDING_FAMILY_PATTERNS`) is what routes it to the row-sharded rule
in the ``EMBEDDING`` preset and to the embedding-aware branches of
``infer_plan`` and FML503.
"""

from __future__ import annotations

import copy
import functools
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from flinkml_tpu.embeddings import exchange
from flinkml_tpu.utils.logging import get_logger

_log = get_logger("embeddings")


def _row_entry(plan, param_name: str):
    """The plan's dim-0 spec entry for the table (None/str/tuple), after
    refusing any layout that splits the row payload."""
    from flinkml_tpu.sharding.plan import entry_axes

    spec = plan.spec_for(param_name, ndim=2)
    for dim_idx, entry in enumerate(spec[1:], start=1):
        if entry_axes(entry):
            raise ValueError(
                f"plan {plan.name!r} shards dim {dim_idx} of embedding "
                f"table {param_name!r} over {entry_axes(entry)}: the "
                "sparse lookup/exchange primitives move WHOLE rows "
                "between shards — shard dim 0 only (the EMBEDDING "
                "preset's layout)"
            )
    return spec[0] if spec else None


def _entry_axes_tuple(entry) -> Tuple[str, ...]:
    from flinkml_tpu.sharding.plan import entry_axes

    return entry_axes(entry)


@functools.lru_cache(maxsize=64)
def _lookup_program(mesh, row_entry, n_shards: int, shard_rows: int):
    """Jitted replicated-ids lookup over a row-sharded table (the
    :func:`~flinkml_tpu.embeddings.exchange.psum_lookup` program)."""
    import jax
    from jax.sharding import PartitionSpec as P

    axes = _entry_axes_tuple(row_entry)
    axes_arg = axes if len(axes) > 1 else axes[0]

    def local(table_shard, ids):
        return exchange.psum_lookup(
            table_shard, ids, axes=axes_arg, shard_rows=shard_rows
        )

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(row_entry), P()), out_specs=P(),
    ))


@functools.lru_cache(maxsize=64)
def _scatter_program(mesh, row_entry, n_shards: int, shard_rows: int,
                     strategy: str):
    """Jitted sharded scatter-add: the global delta batch arrives split
    over the row axes (each shard routes ITS slice of the batch), so
    per-step traffic is batch-sized regardless of vocab."""
    import jax
    from jax.sharding import PartitionSpec as P

    axes = _entry_axes_tuple(row_entry)
    axes_arg = axes if len(axes) > 1 else axes[0]

    def local(table_shard, ids, delta):
        (out,) = exchange.scatter_add(
            (table_shard,), ((0, ids, delta),),
            axes=axes_arg, n_shards=n_shards, shard_rows=shard_rows,
            strategy=strategy)
        return out

    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(row_entry), P(row_entry), P(row_entry)),
        out_specs=P(row_entry),
    ))


@functools.lru_cache(maxsize=64)
def _patch_program(mesh, row_entry, n_shards: int, shard_rows: int):
    """Jitted replicated-ids row SET over a row-sharded table (the
    incremental-publish path): each shard overwrites exactly the rows it
    owns and drops the rest by routing their indices out of range
    (``mode="drop"``). A SET — not an add of a difference — so the
    patched table is bitwise equal to a fresh placement of the patched
    host array, which is what makes delta-published predictions
    bit-identical to a full-snapshot publish."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    axes = _entry_axes_tuple(row_entry)
    axes_arg = axes if len(axes) > 1 else axes[0]

    def local(table_shard, ids, values):
        mask, safe = exchange.owned(ids, axes_arg, shard_rows)
        idx = jnp.where(mask, safe, shard_rows)  # OOB → dropped
        return table_shard.at[idx].set(values, mode="drop")

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(row_entry), P(), P()),
        out_specs=P(row_entry),
    ))


class EmbeddingTable:
    """See the module docstring. ``rows=None`` initializes to zeros (or
    ``scale``-scaled normal rows when ``scale`` is given); a host array
    of shape ``[vocab, dim]`` seeds the table explicitly."""

    def __init__(
        self,
        name: str,
        vocab: int,
        dim: int,
        *,
        mesh=None,
        plan=None,
        dtype=np.float32,
        optimizer_slots: int = 0,
        hbm_budget_bytes: Optional[int] = None,
        rows: Optional[np.ndarray] = None,
        slots: Optional[Sequence[np.ndarray]] = None,
        seed: int = 0,
        scale: Optional[float] = None,
    ):
        from flinkml_tpu.parallel import DeviceMesh
        from flinkml_tpu.sharding.apply import validate_plan
        from flinkml_tpu.sharding.plan import EMBEDDING, REPLICATED, infer_plan

        if vocab < 1 or dim < 1:
            raise ValueError(f"need vocab >= 1 and dim >= 1, got "
                             f"({vocab}, {dim})")
        self.name = str(name)
        self.vocab = int(vocab)
        self.dim = int(dim)
        self.dtype = np.dtype(dtype)
        self.optimizer_slots = int(optimizer_slots)
        self.param_name = f"{self.name}/embedding"

        if plan is None:
            if hbm_budget_bytes is not None:
                # Route through infer_plan: the mesh (given, or the
                # full EMBEDDING-shaped local mesh) decides which preset
                # fits; an over-budget vocab lands on the embedding
                # plan, a small one stays replicated/batch-parallel.
                probe_mesh = mesh or DeviceMesh.for_plan(EMBEDDING)
                plan = infer_plan(
                    probe_mesh, {self.param_name: (self.vocab, self.dim)},
                    hbm_budget_bytes, dtype_bytes=self.dtype.itemsize,
                    optimizer_slots=self.optimizer_slots,
                )
                mesh = mesh or probe_mesh
            else:
                plan = REPLICATED
        self.plan = plan
        self.mesh = mesh or DeviceMesh.for_plan(plan)
        self.row_entry = _row_entry(plan, self.param_name)

        axis_sizes = dict(self.mesh.mesh.shape)
        self.n_shards = 1
        for axis in _entry_axes_tuple(self.row_entry):
            self.n_shards *= int(axis_sizes.get(axis, 1))
        self.shard_rows = exchange.shard_rows_for(self.vocab, self.n_shards)
        self.padded_vocab = self.shard_rows * self.n_shards

        # FML5xx, pre-placement, over the PADDED shape (what is actually
        # laid out) with the optimizer slots counted.
        validate_plan(
            plan, self.mesh,
            param_shapes={self.param_name: (self.padded_vocab, self.dim)},
            hbm_budget_bytes=hbm_budget_bytes,
            dtype_bytes=self.dtype.itemsize,
            optimizer_slots=self.optimizer_slots,
        )

        if rows is None:
            if scale is None:
                host = np.zeros((self.vocab, self.dim), self.dtype)
            else:
                rng = np.random.default_rng(seed)
                host = (rng.standard_normal((self.vocab, self.dim))
                        * float(scale)).astype(self.dtype)
        else:
            host = np.asarray(rows, self.dtype)
            if host.shape != (self.vocab, self.dim):
                raise ValueError(
                    f"rows shape {host.shape} != ({self.vocab}, {self.dim})"
                )
        self.rows = self._place(host)
        if slots is not None:
            if len(slots) != self.optimizer_slots:
                raise ValueError(
                    f"{len(slots)} slot arrays != optimizer_slots="
                    f"{self.optimizer_slots}"
                )
            self.slots = tuple(self._place(np.asarray(s, self.dtype))
                               for s in slots)
        else:
            self.slots = tuple(
                self._place(np.zeros((self.vocab, self.dim), self.dtype))
                for _ in range(self.optimizer_slots)
            )

    # -- placement ---------------------------------------------------------
    @property
    def sharded(self) -> bool:
        return self.n_shards > 1

    def _sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh.mesh, P(self.row_entry))

    def _place(self, host: np.ndarray):
        """Pad the host ``[vocab, dim]`` array to the shard grid and
        ``device_put`` it row-sharded per the plan."""
        import jax

        pad = self.padded_vocab - host.shape[0]
        if pad:
            host = np.concatenate(
                [host, np.zeros((pad, host.shape[1]), host.dtype)]
            )
        return jax.device_put(host, self._sharding())

    # -- access ------------------------------------------------------------
    def lookup(self, ids):
        """Rows for (replicated) global ``ids`` — exact, and bitwise
        identical at every world size (see
        :func:`~flinkml_tpu.embeddings.exchange.psum_lookup`)."""
        import jax.numpy as jnp

        ids = jnp.asarray(ids, jnp.int32)
        if not self.sharded:
            return self.rows[ids]
        program = _lookup_program(
            self.mesh.mesh, self.row_entry, self.n_shards, self.shard_rows
        )
        return program(self.rows, ids)

    def scatter_add(self, ids, delta, strategy: Optional[str] = None):
        """``rows[ids] += delta`` through the strategy-gated exchange:
        the ``[m]`` id / ``[m, dim]`` delta batch is split over the
        shards (each routes its slice), so traffic is batch-sized. Pads
        with id-0/delta-0 no-op rows to the shard grid. Returns self."""
        import jax.numpy as jnp

        if strategy is not None and strategy not in exchange.STRATEGIES:
            # Validate BEFORE the unsharded early-return: a typo'd
            # strategy developed against a small table must fail here,
            # not first in production sharded use.
            raise ValueError(
                f"unknown exchange strategy {strategy!r}; expected one "
                f"of {exchange.STRATEGIES}"
            )
        ids = np.asarray(ids, np.int32)
        delta = np.asarray(delta, self.dtype)
        if ids.shape[0] != delta.shape[0]:
            raise ValueError(f"{ids.shape[0]} ids != {delta.shape[0]} rows")
        if not self.sharded:
            self.rows = self.rows.at[jnp.asarray(ids)].add(
                jnp.asarray(delta))
            return self
        if strategy is None:
            strategy = exchange.resolve_exchange(self.vocab, self.n_shards)
            if strategy == "dense_psum":  # sharded table: exchange anyway
                strategy = exchange.exchange_strategy()
        pad = (-ids.shape[0]) % self.n_shards
        if pad:
            ids = np.concatenate([ids, np.zeros(pad, np.int32)])
            delta = np.concatenate(
                [delta, np.zeros((pad, self.dim), self.dtype)]
            )
        program = _scatter_program(
            self.mesh.mesh, self.row_entry, self.n_shards, self.shard_rows,
            strategy,
        )
        from jax.sharding import NamedSharding, PartitionSpec as P
        import jax

        batch_sh = NamedSharding(self.mesh.mesh, P(self.row_entry))
        self.rows = program(
            self.rows,
            jax.device_put(ids, batch_sh),
            jax.device_put(delta, batch_sh),
        )
        return self

    def to_host(self) -> np.ndarray:
        """The UNPADDED global ``[vocab, dim]`` host array."""
        return np.asarray(self.rows)[: self.vocab]

    # -- incremental row patch (the features delta-publish path) -----------
    def _patched_rows(self, ids, values):
        import jax.numpy as jnp

        ids = np.asarray(ids, np.int32).reshape(-1)
        values = np.asarray(values, self.dtype)
        if values.shape != (ids.shape[0], self.dim):
            raise ValueError(
                f"row values shape {values.shape} != "
                f"({ids.shape[0]}, {self.dim})"
            )
        if ids.shape[0] != np.unique(ids).shape[0]:
            raise ValueError(
                f"row delta for table {self.name!r} has duplicate ids — "
                "SET semantics require one value per row"
            )
        if ids.size and (int(ids.min()) < 0 or int(ids.max()) >= self.vocab):
            raise ValueError(
                f"row delta ids out of range [0, {self.vocab}) for table "
                f"{self.name!r}"
            )
        if not ids.size:
            return self.rows
        if not self.sharded:
            return self.rows.at[jnp.asarray(ids)].set(jnp.asarray(values))
        program = _patch_program(
            self.mesh.mesh, self.row_entry, self.n_shards, self.shard_rows
        )
        return program(self.rows, jnp.asarray(ids), jnp.asarray(values))

    def apply_row_delta(self, ids, values) -> "EmbeddingTable":
        """``rows[ids] = values`` (exact SET, unique ids, plan-respecting:
        sharded tables patch each row on its owning shard only). Rebinds
        ``self.rows`` and returns self — the TRAINER-side form. Serving
        replicas must use :meth:`clone_with_row_delta` instead so a model
        reference snapshotted by an in-flight batch keeps its rows."""
        self.rows = self._patched_rows(ids, values)
        return self

    def clone_with_row_delta(self, ids, values) -> "EmbeddingTable":
        """Functional patch: a shallow clone whose ``rows`` is the
        patched array; slots and layout are shared with self. Device
        buffers are immutable, so the old table — and any in-flight
        batch holding it through the engine's active-model snapshot —
        serves exactly its own version (the PR 8 contract, extended to
        row patches)."""
        patched = self._patched_rows(ids, values)
        clone = copy.copy(self)
        clone.rows = patched
        return clone

    # -- footprint ---------------------------------------------------------
    def per_device_bytes(self) -> int:
        """Per-device bytes of the table plus its optimizer slots under
        the current layout — the number FML503 compares to the budget."""
        return (self.shard_rows * self.dim * self.dtype.itemsize
                * (1 + self.optimizer_slots))

    def exchange_bytes_per_step(self, batch: int,
                                strategy: str = "ring") -> int:
        """Analytic per-step exchange traffic for a ``batch``-id
        gather + scatter round (all shards, both directions) — linear
        in ``batch``, INDEPENDENT of vocab (``tests/test_embeddings.py``
        holds it), so the traffic contract is auditable."""
        if not self.sharded or strategy == "dense_psum":
            # The dense placement's psum moves the whole table.
            return 2 * self.padded_vocab * self.dim * self.dtype.itemsize
        row_bytes = self.dim * self.dtype.itemsize
        id_bytes = 4
        # gather: ids+acc ride P hops (ring) or gather+route (a2a) —
        # both move P * batch rows in total; scatter mirrors it.
        return 2 * self.n_shards * int(batch) * (row_bytes + id_bytes)

    # -- checkpointing -----------------------------------------------------
    def _slot_name(self, i: int) -> str:
        return f"{self.param_name}_slot{i}"

    def state_dict(self) -> Dict[str, np.ndarray]:
        """Host state (unpadded global arrays) keyed by plan-family
        names — what :meth:`save` records and :meth:`restore` expects."""
        out = {self.param_name: self.to_host()}
        for i, slot in enumerate(self.slots):
            out[self._slot_name(i)] = np.asarray(slot)[: self.vocab]
        return out

    def save(self, manager, epoch: int) -> str:
        """Snapshot through ``CheckpointManager.save(..., plan=...)`` —
        layout tags derive from the plan (``sharded:0`` for the table
        and every slot), so the snapshot participates in elastic
        resharded resume like any plan-sharded state."""
        return manager.save(self.state_dict(), epoch, plan=self.plan)

    @classmethod
    def restore(
        cls,
        manager,
        name: str,
        vocab: int,
        dim: int,
        *,
        mesh=None,
        plan=None,
        dtype=np.float32,
        optimizer_slots: int = 0,
        hbm_budget_bytes: Optional[int] = None,
    ) -> Tuple["EmbeddingTable", int]:
        """Restore the newest snapshot onto a possibly DIFFERENT mesh /
        world size (the elastic path): the snapshot's global arrays
        re-pad and re-place for the new layout; the restored
        :meth:`to_host` is bit-equal to the saved one. Returns
        ``(table, epoch)``; raises if the manager holds no snapshot."""
        like = {f"{name}/embedding": np.zeros((vocab, dim), np.dtype(dtype))}
        for i in range(optimizer_slots):
            like[f"{name}/embedding_slot{i}"] = np.zeros(
                (vocab, dim), np.dtype(dtype))
        restored = manager.restore_latest(like)
        if restored is None:
            raise ValueError(
                f"no checkpoint to restore embedding table {name!r} from "
                f"under {manager.directory}"
            )
        state, epoch = restored
        table = cls(
            name, vocab, dim, mesh=mesh, plan=plan, dtype=dtype,
            optimizer_slots=optimizer_slots,
            hbm_budget_bytes=hbm_budget_bytes,
            rows=state[f"{name}/embedding"],
            slots=[state[f"{name}/embedding_slot{i}"]
                   for i in range(optimizer_slots)],
        )
        return table, epoch
