"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no attention or sequence parallelism (SURVEY.md §2.5 —
its models are GLMs/clustering), but this framework's parallel substrate is
designed so model/sequence sharding layers on without changes; these
primitives are that extension, built the TPU way:

  - **Ring attention** (blockwise attention + flash-style online softmax):
    Q stays resident, K/V blocks rotate around the mesh axis via
    ``lax.ppermute`` (XLA lowers to ICI neighbor exchanges that overlap
    with the block matmuls). Peak memory per device is O(L_local²)
    instead of O(L²), so sequence length scales linearly with devices.
  - **Ulysses** (all-to-all sequence parallelism): reshard
    sequence-sharded activations to head-sharded via one ``all_to_all``,
    run ordinary full attention locally per head group, reshard back.
    Cheaper collectives for moderate L; requires heads % devices == 0.

Both compute exact attention — tests compare against the single-device
full-softmax reference to float32 tolerance, causal and non-causal.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from flinkml_tpu.parallel.mesh import DeviceMesh

_NEG = -1e30  # finite "-inf": keeps exp()/max() NaN-free on fully masked rows


def _block_update(q, k, v, m, l, o, scale, q_off, k_off, causal):
    """One blockwise attention step with online-softmax accumulators.

    q [B,H,Lq,D] against one K/V block [B,H,Lk,D]; (m, l, o) are the
    running max, normalizer, and unnormalized output.
    """
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        q_pos = q_off + jnp.arange(q.shape[2])
        k_pos = k_off + jnp.arange(k.shape[2])
        mask = q_pos[:, None] >= k_pos[None, :]
        scores = jnp.where(mask, scores, _NEG)
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
    p = jnp.exp(scores - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    o_new = o * alpha + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m_new, l_new, o_new


def _finalize(m, l, o):
    # Rows with no unmasked key (l == 0) return 0 rather than NaN.
    return jnp.where(l > 0, o / jnp.maximum(l, 1e-30), 0.0)


def _ring_attention_local(q, k, v, axis: str, causal: bool):
    """Per-device ring pass. All inputs [B, H, L_local, D], seq-sharded."""
    p_size = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    l_loc = q.shape[2]
    scale = 1.0 / (q.shape[3] ** 0.5)
    perm = [(i, (i + 1) % p_size) for i in range(p_size)]

    # pcast-to-varying: the accumulators are constants, but the loop carry
    # must be marked device-varying to match the per-device outputs.
    m = jax.lax.pcast(
        jnp.full(q.shape[:3] + (1,), _NEG, dtype=q.dtype), (axis,), to="varying"
    )
    l = jax.lax.pcast(
        jnp.zeros(q.shape[:3] + (1,), dtype=q.dtype), (axis,), to="varying"
    )
    o = jnp.zeros_like(q)

    def body(s, carry):
        m, l, o, kb, vb = carry
        # After s forward rotations, this device holds the block that
        # device (idx - s) mod P owns — its global key offset follows.
        src = (jnp.asarray(idx, jnp.int32) - jnp.asarray(s, jnp.int32)
               + p_size) % p_size
        m, l, o = _block_update(
            q, kb, vb, m, l, o, scale, idx * l_loc, src * l_loc, causal
        )
        kb = jax.lax.ppermute(kb, axis, perm)
        vb = jax.lax.ppermute(vb, axis, perm)
        return m, l, o, kb, vb

    m, l, o, _, _ = jax.lax.fori_loop(0, p_size, body, (m, l, o, k, v))
    return _finalize(m, l, o)


def _full_attention(q, k, v, causal: bool, q_off=0):
    """Plain full-softmax attention (the Ulysses local step and the
    single-device fallback)."""
    scale = 1.0 / (q.shape[3] ** 0.5)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        q_pos = q_off + jnp.arange(q.shape[2])
        k_pos = jnp.arange(k.shape[2])
        scores = jnp.where(q_pos[:, None] >= k_pos[None, :], scores, _NEG)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


def _ulysses_local(q, k, v, axis: str, causal: bool):
    """All-to-all reshard: seq-sharded [B,H,L/P,D] -> head-sharded
    [B,H/P,L,D], full attention, reshard back."""
    def seq_to_heads(x):
        return jax.lax.all_to_all(
            x, axis, split_axis=1, concat_axis=2, tiled=True
        )

    def heads_to_seq(x):
        return jax.lax.all_to_all(
            x, axis, split_axis=2, concat_axis=1, tiled=True
        )

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    oh = _full_attention(qh, kh, vh, causal)
    return heads_to_seq(oh)


@functools.lru_cache(maxsize=32)
def _sharded_attention(mesh, axis: str, kind: str, causal: bool):
    local = {
        "ring": _ring_attention_local,
        "ulysses": _ulysses_local,
    }[kind]
    fn = functools.partial(local, axis=axis, causal=causal)
    spec = P(None, None, axis, None)  # [B, H, L, D] sharded on L
    return jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)
    )


def ring_attention(q, k, v, mesh: Optional[DeviceMesh] = None,
                   causal: bool = False):
    """Exact attention over sequence-sharded Q/K/V ``[B, H, L, D]``.

    ``L`` must divide by the mesh size. K/V blocks rotate over the mesh
    axis (``ppermute`` on ICI) with flash-style online-softmax
    accumulation; activations never materialize ``[L, L]`` scores.
    """
    dm = mesh if mesh is not None else DeviceMesh()
    return _dispatch(q, k, v, dm, "ring", causal)


def ulysses_attention(q, k, v, mesh: Optional[DeviceMesh] = None,
                      causal: bool = False):
    """Exact attention via all-to-all sequence→head resharding.

    Requires ``H % mesh_size == 0`` and ``L % mesh_size == 0``.
    """
    dm = mesh if mesh is not None else DeviceMesh()
    return _dispatch(q, k, v, dm, "ulysses", causal)


def _dispatch(q, k, v, dm: DeviceMesh, kind: str, causal: bool):
    if q.shape != k.shape or q.shape != v.shape:
        raise ValueError(f"q/k/v shapes differ: {q.shape} {k.shape} {v.shape}")
    if q.ndim != 4:
        raise ValueError(f"expected [batch, heads, seq, dim], got {q.shape}")
    p_size = dm.axis_size(dm.axis_names[0])
    if q.shape[2] % p_size != 0:
        raise ValueError(
            f"sequence length {q.shape[2]} must divide by mesh size {p_size}"
        )
    if kind == "ulysses" and q.shape[1] % p_size != 0:
        raise ValueError(
            f"ulysses needs heads ({q.shape[1]}) divisible by the mesh "
            f"size ({p_size})"
        )
    if p_size == 1:
        return _full_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                               causal)
    fn = _sharded_attention(dm.mesh, dm.axis_names[0], kind, causal)
    return fn(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
