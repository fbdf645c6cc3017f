"""Tensor and pipeline parallelism over named mesh axes.

SURVEY.md §2.5: the reference has no TP/PP (its models are GLMs with one
``double[]`` of state), but the mesh substrate must expose the axes so
model sharding layers on. These are those layers, in the standard TPU
formulation — shardings + compiler-inserted or explicit collectives, not
message passing:

  - **Column-parallel linear** (Megatron fan-out): weights ``[d_in,
    d_out]`` sharded on d_out; activations replicated in; outputs sharded.
    No communication in the forward pass.
  - **Row-parallel linear** (fan-in): weights sharded on d_in; activations
    sharded in; one ``psum`` over the model axis produces replicated
    outputs. Composing column→row gives the classic 2-collective MLP
    block.
  - **Pipeline stages**: layer params stacked on the pipeline axis, each
    device applies its stage and ``ppermute``s activations to the next —
    a GPipe-style microbatch loop with ICI neighbor hops.

All primitives work on any mesh whose axis names include the given one,
so they compose with the data axis (e.g. ``{"data": 2, "model": 4}``).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from flinkml_tpu.parallel.mesh import DeviceMesh


def _axis_check(dm: DeviceMesh, axis: str) -> int:
    if axis not in dm.axis_names:
        raise ValueError(
            f"mesh has axes {dm.axis_names}, no axis named {axis!r}"
        )
    return dm.axis_size(axis)


@functools.lru_cache(maxsize=64)
def _mlp_fn(mesh, axis: str, activation_name: str):
    activation = getattr(jax.nn, activation_name)

    def local(x, w1, b1, w2, b2):
        # Column-parallel: local [d, d_ff/P] slice — no comm.
        h = activation(x @ w1 + b1)
        # Row-parallel: local partial product, then one psum.
        return jax.lax.psum(h @ w2, axis) + b2

    return jax.jit(
        jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(
                P(),            # x replicated over the model axis
                P(None, axis),  # w1 [d_in, d_ff] sharded on d_ff
                P(axis),        # b1 [d_ff]
                P(axis, None),  # w2 [d_ff, d_out] sharded on d_ff
                P(),            # b2 [d_out] replicated
            ),
            out_specs=P(),
        )
    )


def tensor_parallel_mlp(x, w1, b1, w2, b2, mesh: Optional[DeviceMesh] = None,
                        axis: str = "model", activation: str = "gelu"):
    """The canonical TP block: column-parallel ``w1`` + activation +
    row-parallel ``w2`` with a single ``psum``.

    Shapes: ``x [.., d_in]``, ``w1 [d_in, d_ff]``, ``b1 [d_ff]``,
    ``w2 [d_ff, d_out]``, ``b2 [d_out]``; ``d_ff`` must divide by the
    size of ``axis``. Output replicated over ``axis``.
    """
    dm = mesh if mesh is not None else DeviceMesh({"model": len(jax.devices())})
    p_size = _axis_check(dm, axis)
    d_ff = w1.shape[1]
    if d_ff % p_size != 0:
        raise ValueError(f"d_ff {d_ff} must divide by axis size {p_size}")
    if w2.shape[0] != d_ff or b1.shape[0] != d_ff:
        raise ValueError("w1/b1/w2 d_ff dimensions disagree")
    fn = _mlp_fn(dm.mesh, axis, activation)
    return fn(jnp.asarray(x), jnp.asarray(w1), jnp.asarray(b1),
              jnp.asarray(w2), jnp.asarray(b2))


@functools.lru_cache(maxsize=64)
def _pipeline_fn(mesh, axis: str, stage: Callable, n_microbatches: int):
    # Cache key includes the stage FUNCTION, so re-registering a name with
    # a new function compiles fresh instead of silently reusing the old one.

    def local(x_mb, params):
        """x_mb: [n_microbatches, ...] (replicated); params: [1, ...] —
        this device's stage slice of the stage-sharded stack. GPipe
        schedule: at step t, device s processes microbatch (t - s);
        activations ppermute forward one hop per step."""
        params = params[0]  # drop the sharded stage dim (1 per device)
        p_size = jax.lax.axis_size(axis)
        idx = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % p_size) for i in range(p_size)]
        n_steps = n_microbatches + p_size - 1

        def body(t, carry):
            acts, outputs = carry
            # Device s works on the microbatch that entered at t - s.
            mb = t - jnp.asarray(idx, jnp.int32)
            active = (mb >= 0) & (mb < n_microbatches)
            processed = stage(acts, params)
            acts_new = jnp.where(active, processed, acts)
            # Last stage banks its finished microbatch.
            is_last = idx == p_size - 1
            bank = jnp.clip(mb, 0, n_microbatches - 1)
            outputs = jnp.where(
                active & is_last,
                outputs.at[bank].set(acts_new),
                outputs,
            )
            # Rotate activations to the next stage; stage 0 loads the next
            # incoming microbatch instead of the wrap-around payload.
            rotated = jax.lax.ppermute(acts_new, axis, perm)
            nxt = jnp.clip(t + 1, 0, n_microbatches - 1)
            acts = jnp.where(
                (idx == 0) & (t + 1 < n_microbatches), x_mb[nxt], rotated
            )
            return acts, outputs

        # pcast-to-varying: inputs are replicated but the carry becomes
        # device-varying after the first rotation.
        init_acts = jax.lax.pcast(x_mb[0], (axis,), to="varying")
        outputs = jax.lax.pcast(
            jnp.zeros((n_microbatches,) + x_mb[0].shape, dtype=x_mb.dtype),
            (axis,), to="varying",
        )
        _, outputs = jax.lax.fori_loop(0, n_steps, body, (init_acts, outputs))
        # Only the last stage banked real outputs; psum-mask replicates them.
        last = p_size - 1
        return jax.lax.psum(
            jnp.where(jax.lax.axis_index(axis) == last, outputs, 0.0), axis
        )

    return jax.jit(
        jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(axis)),   # microbatches replicated; params staged
            out_specs=P(),
        )
    )


# Pipeline stages must be named (hashable for the jit cache) pure fns
# (acts, params) -> acts.
_STAGE_REGISTRY: dict = {}


def register_pipeline_stage(name: str, fn: Callable) -> None:
    """Register a stage function ``(acts, params) -> acts`` for
    :func:`pipeline_parallel_apply`."""
    _STAGE_REGISTRY[name] = fn


register_pipeline_stage(
    "linear_tanh", lambda a, p: jnp.tanh(a @ p)
)


@functools.lru_cache(maxsize=64)
def _expert_fn(mesh, axis: str, activation_name: str):
    activation = getattr(jax.nn, activation_name)

    def local(x, gates, w1, w2):
        # One expert slice per device ([1, ...] of the expert-stacked
        # weights); dense dispatch: every device evaluates its expert on
        # all tokens, the gate mask + psum combine (exact MoE; the
        # all-to-all capacity-routed variant is an optimization on top).
        e = jax.lax.axis_index(axis)
        h = activation(x @ w1[0])
        y = h @ w2[0]
        return jax.lax.psum(gates[:, e][:, None] * y, axis)

    return jax.jit(
        jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(), P(axis), P(axis)),
            out_specs=P(),
        )
    )


def expert_parallel_ffn(x, gates, w1, w2, mesh: Optional[DeviceMesh] = None,
                        axis: str = "expert", activation: str = "gelu"):
    """Expert-parallel mixture-of-experts FFN: expert e's weights live on
    device e of ``axis``; outputs are the gate-weighted sum of expert
    outputs (one ``psum``).

    Shapes: ``x [n, d_in]``, ``gates [n, E]`` (rows of mixture weights,
    e.g. a softmax or a one-hot top-1), ``w1 [E, d_in, d_ff]``,
    ``w2 [E, d_ff, d_out]``; ``E`` must equal the size of ``axis``.
    """
    dm = mesh if mesh is not None else DeviceMesh({"expert": len(jax.devices())})
    p_size = _axis_check(dm, axis)
    e = w1.shape[0]
    if e != p_size or w2.shape[0] != e or gates.shape[1] != e:
        raise ValueError(
            f"expert count mismatch: w1 {w1.shape[0]}, w2 {w2.shape[0]}, "
            f"gates {gates.shape[1]}, axis size {p_size}"
        )
    fn = _expert_fn(dm.mesh, axis, activation)
    return fn(jnp.asarray(x), jnp.asarray(gates), jnp.asarray(w1),
              jnp.asarray(w2))


@functools.lru_cache(maxsize=64)
def _routed_expert_fn(mesh, axis: str, capacity: int, activation_name: str):
    activation = getattr(jax.nn, activation_name)

    def local(xl, logits_l, w1, w2):
        """Switch-style top-1 routed MoE. xl [n_loc, d] token-sharded;
        logits_l [n_loc, E]; w1/w2 [1, ...] — this device's expert."""
        n_loc, d = xl.shape
        e_count = logits_l.shape[1]
        probs = jax.nn.softmax(logits_l, axis=-1)
        expert = jnp.argmax(probs, axis=-1)                 # [n]
        gate = jnp.max(probs, axis=-1)                      # [n]
        onehot = jax.nn.one_hot(expert, e_count, dtype=xl.dtype)  # [n, E]
        # 0-based rank of each token within its expert's send buffer;
        # tokens beyond capacity are dropped (their combine weight is 0).
        # Rank bookkeeping runs in int32 regardless of the data dtype —
        # a bf16 cumsum cannot count past 256 and would silently collide
        # buffer slots.
        onehot_i = jax.nn.one_hot(expert, e_count, dtype=jnp.int32)
        ranks = jnp.cumsum(onehot_i, axis=0) * onehot_i      # [n, E]: rank+1
        pos_tok = jnp.sum(ranks, axis=1) - 1                 # [n]
        keep_tok = pos_tok < capacity
        # one_hot(-1) is all-zeros, which zeroes dropped tokens out of the
        # dispatch AND the combine.
        poshot = jax.nn.one_hot(
            jnp.where(keep_tok, pos_tok, -1), capacity, dtype=xl.dtype
        )                                                    # [n, C]
        mask = onehot[:, :, None] * poshot[:, None, :]       # [n, E, C]
        dispatch = jnp.einsum("nec,nd->ecd", mask, xl)       # [E, C, d]
        # Exchange: device p receives every peer's buffer for expert p.
        recv = jax.lax.all_to_all(
            dispatch, axis, split_axis=0, concat_axis=0, tiled=True
        )                                                    # [P, C, d]
        h = activation(recv.reshape(-1, d) @ w1[0])
        y = (h @ w2[0]).reshape(recv.shape[0], capacity, -1)
        back = jax.lax.all_to_all(
            y, axis, split_axis=0, concat_axis=0, tiled=True
        )                                                    # [E, C, d_out]
        combined = jnp.einsum("nec,ecd->nd", mask, back)
        return combined * gate[:, None]

    return jax.jit(
        jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis)),
            out_specs=P(axis),
        )
    )


def routed_expert_ffn(x, router_logits, w1, w2,
                      mesh: Optional[DeviceMesh] = None,
                      axis: str = "expert", capacity_factor: float = 1.25,
                      activation: str = "gelu"):
    """Top-1 routed expert-parallel MoE (Switch-style): tokens are
    dispatched to their expert's device over one ``all_to_all``, processed
    there, and returned by a second ``all_to_all`` — communication scales
    with tokens actually routed, not tokens × experts.

    Shapes: ``x [n, d_in]`` (token-sharded over ``axis``),
    ``router_logits [n, E]``, ``w1 [E, d_in, d_ff]``, ``w2 [E, d_ff,
    d_out]``; ``E`` must equal the axis size and ``n`` divide by it.
    Per-device-per-expert capacity = ``ceil(n_local / E *
    capacity_factor)``; over-capacity tokens are dropped (zero output),
    the standard Switch behavior.
    """
    dm = mesh if mesh is not None else DeviceMesh({"expert": len(jax.devices())})
    p_size = _axis_check(dm, axis)
    n, e_count = router_logits.shape[0], router_logits.shape[1]
    if e_count != p_size or w1.shape[0] != e_count or w2.shape[0] != e_count:
        raise ValueError(
            f"expert count mismatch: logits {e_count}, w1 {w1.shape[0]}, "
            f"w2 {w2.shape[0]}, axis size {p_size}"
        )
    if n % p_size != 0 or x.shape[0] != n:
        raise ValueError(
            f"token count {n} must match x rows {x.shape[0]} and divide by "
            f"the mesh size {p_size}"
        )
    n_local = n // p_size
    capacity = max(1, math.ceil(n_local * capacity_factor / p_size))
    fn = _routed_expert_fn(dm.mesh, axis, capacity, activation)
    return fn(jnp.asarray(x), jnp.asarray(router_logits), jnp.asarray(w1),
              jnp.asarray(w2))


def pipeline_parallel_apply(x_microbatches, stage_params, stage: str,
                            mesh: Optional[DeviceMesh] = None,
                            axis: str = "pipe"):
    """GPipe-style pipeline over ``axis``: device s applies stage s.

    Args:
        x_microbatches: ``[n_microbatches, ...]`` inputs (replicated).
        stage_params: ``[n_stages, ...]`` per-stage params, sharded on
            ``axis`` (n_stages must equal the axis size).
        stage: name registered via :func:`register_pipeline_stage`.
    Returns:
        ``[n_microbatches, ...]`` outputs after all stages, replicated.
    """
    dm = mesh if mesh is not None else DeviceMesh({"pipe": len(jax.devices())})
    p_size = _axis_check(dm, axis)
    if stage_params.shape[0] != p_size:
        raise ValueError(
            f"stage_params has {stage_params.shape[0]} stages but axis "
            f"{axis!r} has {p_size} devices"
        )
    if stage not in _STAGE_REGISTRY:
        raise ValueError(f"unknown pipeline stage {stage!r}")
    n_mb = int(x_microbatches.shape[0])
    fn = _pipeline_fn(dm.mesh, axis, _STAGE_REGISTRY[stage], n_mb)
    return fn(jnp.asarray(x_microbatches), jnp.asarray(stage_params))
