"""Pass 6 — sorted-scatter provenance validation (FML404), pre-compile.

The sorted-layout contract (``docs/development/kernels.md``): sortedness
is bought ONCE at pack time — :class:`~flinkml_tpu.table
.SortedSparseColumn` carries ``indices_are_sorted=True`` as recorded
provenance — so every downstream gradient scatter is entitled to the
``indices_are_sorted=True`` fast path for free. A ``segment_sum`` (or
any scatter-add) traced with ``indices_are_sorted=False`` over indices
that CAME from a sorted-provenance source silently re-pays the sort the
pipeline already performed: XLA lowers the unsorted scatter through the
general sort-and-combine path, and the pack-time work is wasted on
every step, forever, with no error anywhere. That is FML404.

Device-free: the check walks jaxprs (``jax.make_jaxpr``), propagating a
**sorted** flag from the declared sorted inputs through the
order-preserving ops (reshape / broadcast / cast / slice / copy — the
ops the ``segment_sum`` expansion itself applies to its ids) and one
level of call primitives, and flags every scatter-add whose
scatter-indices operand is sorted-provenance while its
``indices_are_sorted`` param is ``False``.

Consumes live functions pre-compile (:func:`check_sorted_scatter_fn`)
or ``*.scatter.json`` declarative probes (:func:`check_scatter_file`,
routed by ``python -m flinkml_tpu.analysis``):

.. code-block:: json

    {"program": {"name": "segment_sum", "cells": 64, "num_segments": 16,
                 "indices_are_sorted": false},
     "sorted_guarantee": true}

``sorted_guarantee`` declares the probe's ids input as pack-time sorted
(the SortedSparseColumn provenance); ``indices_are_sorted`` is the flag
the traced scatter actually passes. ``true``/``false`` → FML404.
"""

from __future__ import annotations

import json
from typing import Iterable, List, Optional, Sequence

from flinkml_tpu.analysis.findings import Finding

#: Primitives through which sorted provenance propagates: they preserve
#: element order along the (single) sorted axis. Gathers/permutes are
#: deliberately absent — ``take(ids, perm)`` yields an arbitrary order
#: unless perm itself is the sorting permutation, which this static
#: pass cannot see.
ORDER_PRESERVING = frozenset({
    "reshape",
    "broadcast_in_dim",
    "convert_element_type",
    "squeeze",
    "slice",
    "dynamic_slice",
    "copy",
    "stop_gradient",
})

#: Call primitives recursed one level (the gate / jit wrappers the
#: sparse trainers put around their scatters).
_CALL_PRIMITIVES = frozenset({"jit", "closed_call", "core_call",
                              "custom_jvp_call", "custom_vjp_call",
                              "remat", "checkpoint"})

_SCATTER_ADD = "scatter-add"


def _is_var(v) -> bool:
    """True for jaxpr Vars (hashable, trackable); False for Literals
    (inline constants — they carry ``.val`` and are unhashable)."""
    return not hasattr(v, "val")


def _subjaxprs(params) -> Iterable:
    for v in params.values():
        if hasattr(v, "jaxpr") and hasattr(v, "consts"):  # ClosedJaxpr
            yield v.jaxpr
        elif hasattr(v, "eqns"):  # raw Jaxpr
            yield v


def _walk(jaxpr, sorted_vars: set, location: Optional[str],
          findings: List[Finding], depth: int = 0) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == _SCATTER_ADD:
            idx_var = eqn.invars[1]  # (operand, scatter_indices, updates)
            if (not eqn.params.get("indices_are_sorted", False)
                    and _is_var(idx_var) and idx_var in sorted_vars):
                findings.append(Finding(
                    "FML404",
                    "scatter-add traced with indices_are_sorted=False "
                    "over indices with pack-time sorted provenance: the "
                    "pipeline already sorted these ids (SortedSparseColumn "
                    "contract) and this scatter re-pays the sort on every "
                    "step",
                    location=location,
                    fix_hint="pass indices_are_sorted=True to segment_sum "
                             "(read the column's indices_are_sorted "
                             "attribute instead of hardcoding False)",
                ))
        elif name in ORDER_PRESERVING:
            if any(_is_var(v) and v in sorted_vars for v in eqn.invars):
                sorted_vars.update(eqn.outvars)
        elif name in _CALL_PRIMITIVES and depth < 1:
            for sub in _subjaxprs(eqn.params):
                inner_sorted = {
                    iv for iv, ov in zip(sub.invars, eqn.invars)
                    if _is_var(ov) and ov in sorted_vars
                }
                # Approximation: invars of jit map positionally onto
                # the sub-jaxpr's invars (true for the wrappers we
                # recurse; consts ride constvars).
                _walk(sub, inner_sorted | sorted_vars, location,
                      findings, depth + 1)


def check_sorted_scatter_jaxpr(closed_jaxpr, sorted_argnums: Sequence[int],
                               location: Optional[str] = None
                               ) -> List[Finding]:
    """FML404 findings for a closed jaxpr whose inputs at
    ``sorted_argnums`` carry the pack-time sorted guarantee."""
    jaxpr = closed_jaxpr.jaxpr
    sorted_vars = {jaxpr.invars[i] for i in sorted_argnums
                   if i < len(jaxpr.invars)}
    findings: List[Finding] = []
    _walk(jaxpr, sorted_vars, location, findings)
    return findings


def check_sorted_scatter_fn(fn, args, sorted_argnums: Sequence[int],
                            location: Optional[str] = None
                            ) -> List[Finding]:
    """Trace ``fn(*args)`` (abstract, device-free) and run the FML404
    walk with the arguments at ``sorted_argnums`` declared as sorted-
    provenance inputs (a SortedSparseColumn's ``segment_ids``, a
    pack-time ``ell_sort_tables`` output, ...)."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    return check_sorted_scatter_jaxpr(closed, sorted_argnums, location)


def _probe_program(program: dict):
    """Build the declarative probe named by ``program`` — a tiny traced
    function plus its abstract args and which argnum is the ids input.

    ``segment_sum``: the gradient-scatter shape itself.
    ``gathered_segment_sum``: the SortedSparseColumn consumer shape —
    ``segment_sum(take(contrib, perm), segment_ids, ...)`` (the gather
    permutes VALUES, not ids; the ids input keeps its provenance).
    """
    import jax.numpy as jnp

    name = program.get("name", "segment_sum")
    cells = int(program.get("cells", 64))
    num_segments = int(program.get("num_segments", 16))
    flag = bool(program.get("indices_are_sorted", False))
    vals = jnp.zeros(cells, jnp.float32)
    ids = jnp.zeros(cells, jnp.int32)
    if name == "segment_sum":
        import jax

        def fn(v, i):
            return jax.ops.segment_sum(v, i, num_segments=num_segments,
                                       indices_are_sorted=flag)

        return fn, (vals, ids), 1
    if name == "gathered_segment_sum":
        import jax

        perm = jnp.zeros(cells, jnp.int32)

        def fn(v, p, i):
            return jax.ops.segment_sum(jnp.take(v, p), i,
                                       num_segments=num_segments,
                                       indices_are_sorted=flag)

        return fn, (vals, perm, ids), 2
    raise ValueError(f"unknown scatter probe program {name!r} "
                     "(known: segment_sum, gathered_segment_sum)")


def check_scatter_file(path: str) -> List[Finding]:
    """Validate a ``*.scatter.json`` probe (schema in the module
    docstring). Unreadable or malformed files report one FML404
    finding naming the path — the gate must fail loudly, not skip
    silently."""
    try:
        with open(path, "r") as fh:
            doc = json.load(fh)
        program = doc["program"]
        sorted_guarantee = bool(doc.get("sorted_guarantee", False))
        fn, args, ids_argnum = _probe_program(program)
    except (OSError, ValueError, KeyError, TypeError) as e:
        return [Finding(
            "FML404",
            f"sorted-scatter file {path} is unreadable or malformed: "
            f"{e!r}",
            location=path,
            fix_hint="see flinkml_tpu/analysis/sorted_scatter.py for "
                     "the *.scatter.json schema",
        )]
    sorted_argnums = (ids_argnum,) if sorted_guarantee else ()
    return check_sorted_scatter_fn(fn, args, sorted_argnums, location=path)
