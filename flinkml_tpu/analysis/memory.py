"""Pass 7 — static per-device memory-liveness analysis (FML70x).

HBM capacity is the axis the rest of the analyzer reasons about worst:
FML503 screens parameters + optimizer slots at one scalar width, blind
to per-leaf precision, the int8 tier, and every activation a program
materializes. This pass walks jaxprs device-free (``jax.make_jaxpr``,
recursing jit/scan/while/cond exactly like the precision pass) and
computes a **per-device peak-live-bytes estimate** for a program under
a ``(ShardingPlan, quant tier)`` pair:

  - **parameters + optimizer slots** are sized per LEAF from the traced
    avals (the actual storage widths — a bf16-stored momentum costs
    2 B/elem, an int8 table 1 B/elem), sharded through the same per-dim
    ceil as :func:`~flinkml_tpu.sharding.plan.shard_slice_elems`, so
    this model, FML503, and the :class:`~flinkml_tpu.embeddings
    .EmbeddingTable` padded layout agree at every budget boundary;
  - **activation liveness** runs over the equation schedule: a value's
    buffer is live from the eqn that produces it to its last use, peak
    = the maximum of the live set over the schedule (undonated argument
    buffers are resident for the whole program — XLA cannot reuse a
    buffer the caller still owns);
  - **batch-sharded intermediates** divide their leading dim by the
    plan's batch-axes product (ceil) — the SPMD layout data-parallel
    activations actually get.

Rules:

  - **FML701** — the estimated peak exceeds the per-device HBM budget
    (the activation-aware generalization of FML503, which stays as the
    fast params-only screen).
  - **FML702** — a vocab-scale intermediate is materialized on the hot
    path: an eqn output carrying a full embedding-table extent (a
    one-hot densification, a full-table gather/psum/dequant) where the
    embedding contract promises batch-sized payloads. State OUTPUTS are
    exempt (a scatter-add'd new table is the update, not a leak).
  - **FML703** — a same-shape parameter/carry update whose input buffer
    is not donated: the old and new state coexist at exactly the peak
    moment, doubling state memory for the price of a missing
    ``donate_argnums``.
  - **FML704** — no quant tier in the f32 -> bf16 -> int8 ladder fits
    the budget under any candidate plan; the finding lists every tier's
    footprint (:class:`~flinkml_tpu.sharding.plan.NoFeasiblePlanError`
    rendered as a finding).

The estimate is **measured, not guessed**: ``tests/test_analysis_memory.py``
pins it against XLA's own ``Compiled.memory_analysis()`` (temp +
argument + output bytes) on the fused 5-stage chain's arithmetic and the
plan-sharded SGD step, and fails outside a 0.5x-2.0x band.

Inputs come from live functions pre-compile (:func:`check_memory_fn`,
:func:`estimate_fn_memory`) or ``*.memory.json`` fixtures
(:func:`check_memory_file`, routed by ``python -m flinkml_tpu
.analysis``). See ``docs/development/static_analysis.md``.
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import (
    Any,
    Dict,
    List,
    Mapping,
    Optional,
    Sequence,
    Tuple,
)

import numpy as np

from flinkml_tpu.analysis.findings import Finding
from flinkml_tpu.sharding.plan import (
    NoFeasiblePlanError,
    QUANT_TIER_LADDER,
    REPLICATED,
    PRESETS,
    ShardingPlan,
    _axis_sizes,
    human_bytes,
    infer_plan,
    is_embedding_param,
    shard_slice_elems,
)

#: Same-shape update leaves smaller than this are not worth a donation
#: finding: donating a scalar step counter saves nothing, and the loss
#: scalar would false-positive against it.
DONATION_MIN_ELEMS = 256

#: Leading extents below this never count as "vocab-scale" — a tiny
#: test table's whole-row intermediate is not the densification shape.
VOCAB_SCALE_MIN_ROWS = 1024


@dataclasses.dataclass(frozen=True)
class MemoryEstimate:
    """One program's per-device memory model under a plan.

    ``peak_bytes`` is the headline: the maximum, over the equation
    schedule, of resident (undonated arguments + already-produced
    outputs) plus live intermediates plus control-flow scratch.
    ``argument_bytes``/``output_bytes``/``param_bytes`` break the
    resident set down; ``temp_peak_bytes`` is the intermediate-only
    peak (the analogue of XLA's ``temp_size_in_bytes``)."""

    peak_bytes: int
    argument_bytes: int
    output_bytes: int
    param_bytes: int
    temp_peak_bytes: int

    def render(self) -> str:
        return (
            f"peak {human_bytes(self.peak_bytes)}/device "
            f"(arguments {human_bytes(self.argument_bytes)}, of which "
            f"params+slots {human_bytes(self.param_bytes)}; outputs "
            f"{human_bytes(self.output_bytes)}; intermediate peak "
            f"{human_bytes(self.temp_peak_bytes)})"
        )


def _dtype_itemsize(dtype) -> int:
    try:
        return int(np.dtype(dtype).itemsize)
    except TypeError:
        return 2 if "bfloat16" in str(dtype) else 4


def _is_var(atom) -> bool:
    # Literals are unhashable in some jax versions — never dict keys.
    return hasattr(atom, "aval") and type(atom).__name__ != "Literal"


class _LiveWalk:
    """One liveness walk over a closed jaxpr and its sub-jaxprs,
    accumulating the peak-live estimate and the FML702 sites."""

    def __init__(self, plan: ShardingPlan, axis_sizes: Mapping[str, int],
                 vocab_extents: frozenset):
        self.plan = plan
        self.axis_sizes = dict(axis_sizes)
        self.vocab_extents = vocab_extents
        batch = 1
        for axis in plan.batch_axes:
            batch *= int(self.axis_sizes.get(axis, 1))
        self.batch_factor = max(1, batch)
        # (primitive, shape, dtype) of every vocab-scale intermediate.
        self.vocab_sites: List[Tuple[str, Tuple[int, ...], str]] = []
        self._seen_sites: set = set()

    # -- sizing ------------------------------------------------------------
    def param_bytes(self, name: str, aval) -> int:
        """A named parameter leaf: sharded by its plan family spec."""
        elems = shard_slice_elems(
            self.plan, self.axis_sizes, name, aval.shape
        )
        return elems * _dtype_itemsize(aval.dtype)

    def value_bytes(self, aval) -> int:
        """An activation/intermediate: leading dim divides (ceil) by the
        plan's batch-axes product — the layout data-parallel activations
        get under SPMD; trailing dims stay whole."""
        shape = tuple(getattr(aval, "shape", ()))
        if not shape:
            return _dtype_itemsize(getattr(aval, "dtype", np.float32))
        elems = math.ceil(int(shape[0]) / self.batch_factor)
        for d in shape[1:]:
            elems *= int(d)
        return elems * _dtype_itemsize(aval.dtype)

    # -- FML702 ------------------------------------------------------------
    def _note_vocab_site(self, eqn, exempt_outvars: frozenset) -> None:
        if not self.vocab_extents:
            return
        for ov in eqn.outvars:
            if ov in exempt_outvars or not hasattr(ov, "aval"):
                continue
            shape = tuple(getattr(ov.aval, "shape", ()))
            hit = [d for d in shape if d in self.vocab_extents]
            if not hit:
                continue
            # One finding per offending SHAPE: a one-hot densification
            # drags a convert/transpose/dot train behind it, and six
            # findings for one leak is noise, not signal.
            key = shape
            if key in self._seen_sites:
                continue
            self._seen_sites.add(key)
            self.vocab_sites.append(
                (eqn.primitive.name, shape, str(ov.aval.dtype))
            )

    # -- the walk ----------------------------------------------------------
    def walk(self, jaxpr, invar_bytes: Sequence[int],
             freeable_invars: Sequence[bool],
             exempt_outvars: frozenset = frozenset()) -> Tuple[int, int]:
        """Peak live bytes of one (open) jaxpr given per-invar sizes.

        ``freeable_invars[i]`` marks invar ``i``'s buffer as freeable at
        its last use (donated argument, or an operand owned by the
        enclosing scope's schedule); undonated top-level arguments are
        resident to the end. An eqn's OUTPUT may reuse the buffer of a
        freeable operand dying at that eqn — XLA's buffer assignment
        does exactly this for the fused elementwise trains the 5-stage
        chain compiles to, and it is what ``donate_argnums`` buys for a
        state update (the new state is written over the old). Undonated
        arguments are never reusable (the caller still owns them) —
        which is why a missed donation shows up as a bigger peak
        (FML703). ``exempt_outvars`` are vars whose materialization is
        sanctioned state output (FML702 exemption). Returns
        ``(peak, temp_peak)`` where ``temp_peak`` excludes the resident
        argument floor."""
        last_use: Dict[Any, int] = {}
        for k, eqn in enumerate(jaxpr.eqns):
            for a in eqn.invars:
                if _is_var(a):
                    last_use[a] = k
        outvar_set = frozenset(v for v in jaxpr.outvars if _is_var(v))

        sizes: Dict[Any, int] = {}
        freeable: Dict[Any, bool] = {}
        live = 0
        for var, nbytes, free in zip(jaxpr.invars, invar_bytes,
                                     freeable_invars):
            sizes[var] = int(nbytes)
            freeable[var] = bool(free) and var not in outvar_set
            live += int(nbytes)
        resident_floor = sum(
            sizes[v] for v in jaxpr.invars if not freeable[v]
        )
        peak = live
        for k, eqn in enumerate(jaxpr.eqns):
            self._note_vocab_site(eqn, exempt_outvars)
            out_bytes = 0
            for ov in eqn.outvars:
                if not hasattr(ov, "aval"):
                    continue
                nbytes = self.value_bytes(ov.aval)
                sizes[ov] = nbytes
                freeable[ov] = ov not in outvar_set
                out_bytes += nbytes
            scratch = self._eqn_scratch(eqn, sizes, exempt_outvars)
            # Buffer reuse: a freeable operand dying HERE donates its
            # buffer to the output (up to the output's size).
            dying = sum(
                sizes[a]
                for a in set(a for a in eqn.invars if _is_var(a))
                if last_use.get(a) == k and freeable.get(a, False)
                and a in sizes
            )
            peak = max(peak, live + max(0, out_bytes - dying) + scratch)
            live += out_bytes
            for a in eqn.invars:
                if (_is_var(a) and last_use.get(a) == k
                        and freeable.get(a, False) and a in sizes):
                    live -= sizes.pop(a)
                    freeable[a] = False  # freed once
        peak = max(peak, live)
        return peak, max(0, peak - resident_floor)

    def _eqn_scratch(self, eqn, sizes: Dict[Any, int],
                     exempt_outvars: frozenset) -> int:
        """Extra scratch a control-flow/call eqn needs beyond its
        operand and output buffers: the sub-program's own intermediate
        peak. Operand buffers alias the outer live set, so the inner
        peak is discounted by the operand bytes already counted."""
        name = eqn.primitive.name
        params = eqn.params
        operand_bytes = sum(
            sizes.get(a, 0) for a in eqn.invars if _is_var(a)
        )
        inner_exempt = frozenset()
        if any(ov in exempt_outvars for ov in eqn.outvars):
            # Direct chain: a jit whose outputs ARE the program's state
            # outputs passes the exemption to its sub-jaxpr outvars.
            pass  # handled per-branch below via _map_exempt

        def sub_peak(sub_jaxpr, invar_bytes, exempt=frozenset()):
            inner_free = [True] * len(sub_jaxpr.invars)
            p, _ = self.walk(sub_jaxpr, invar_bytes, inner_free, exempt)
            return p

        def _map_exempt(sub_jaxpr):
            return frozenset(
                iv for iv, ov in zip(sub_jaxpr.outvars, eqn.outvars)
                if _is_var(iv) and ov in exempt_outvars
            ) or inner_exempt

        if name == "scan":
            closed = params["jaxpr"]
            sub = closed.jaxpr
            inner_bytes = [
                self.value_bytes(v.aval) if hasattr(v, "aval") else 0
                for v in sub.invars
            ]
            inner = sub_peak(sub, inner_bytes, _map_exempt(sub))
        elif name == "while":
            body = params["body_jaxpr"].jaxpr
            cond = params["cond_jaxpr"].jaxpr
            body_bytes = [
                self.value_bytes(v.aval) if hasattr(v, "aval") else 0
                for v in body.invars
            ]
            cond_bytes = [
                self.value_bytes(v.aval) if hasattr(v, "aval") else 0
                for v in cond.invars
            ]
            inner = max(sub_peak(body, body_bytes, _map_exempt(body)),
                        sub_peak(cond, cond_bytes))
        elif name == "cond":
            inner = 0
            for br in params["branches"]:
                sub = br.jaxpr
                inner_bytes = [
                    self.value_bytes(v.aval) if hasattr(v, "aval") else 0
                    for v in sub.invars
                ]
                inner = max(inner,
                            sub_peak(sub, inner_bytes, _map_exempt(sub)))
        elif "jaxpr" in params and hasattr(
                getattr(params["jaxpr"], "jaxpr", None), "eqns"):
            sub = params["jaxpr"].jaxpr  # jit / closed_call wrappers
            inner_bytes = [
                (sizes[a] if _is_var(a) and a in sizes
                 else self.value_bytes(v.aval) if hasattr(v, "aval") else 0)
                for a, v in zip(eqn.invars, sub.invars)
            ]
            inner = sub_peak(sub, inner_bytes, _map_exempt(sub))
        elif "call_jaxpr" in params:
            cj = params["call_jaxpr"]
            sub = getattr(cj, "jaxpr", cj)
            inner_bytes = [
                (sizes[a] if _is_var(a) and a in sizes
                 else self.value_bytes(v.aval) if hasattr(v, "aval") else 0)
                for a, v in zip(eqn.invars, sub.invars)
            ]
            inner = sub_peak(sub, inner_bytes, _map_exempt(sub))
        else:
            return 0
        return max(0, inner - operand_bytes)


def _invar_names_roles(closed, example_args, param_argnums):
    """Per-invar (role, name) from the example pytrees — the precision
    pass's labeling, shared verbatim so both passes name leaves the same
    way (and fall back to unlabeled on a structure mismatch)."""
    import jax

    param_set = set(param_argnums)
    roles: List[str] = []
    names: List[str] = []
    for i, arg in enumerate(example_args):
        leaves_with_paths, _ = jax.tree_util.tree_flatten_with_path(arg)
        role = "param" if i in param_set else "data"
        for path, _leaf in leaves_with_paths:
            roles.append(role)
            from flinkml_tpu.analysis.precision import _path_name

            names.append(_path_name(path) or f"arg{i}")
    if len(roles) != len(closed.jaxpr.invars):
        roles = ["data"] * len(closed.jaxpr.invars)
        names = [f"arg{i}" for i in range(len(closed.jaxpr.invars))]
    return roles, names


def estimate_closed_jaxpr(
    closed,
    plan: Optional[ShardingPlan] = None,
    mesh: Optional[Any] = None,
    invar_roles: Optional[Sequence[str]] = None,
    invar_names: Optional[Sequence[str]] = None,
    donate_argnums: Sequence[int] = (),
) -> Tuple[MemoryEstimate, List[Tuple[str, Tuple[int, ...], str]]]:
    """The peak-live estimate for one closed jaxpr, plus the vocab-scale
    sites the walk recorded (for FML702). ``invar_roles`` labels each
    invar ``"param"``/``"data"``; ``donate_argnums`` indexes INVARS
    whose buffers the caller donates."""
    plan = plan if plan is not None else REPLICATED
    axis_sizes = _axis_sizes(mesh) if mesh is not None else {}
    jaxpr = closed.jaxpr
    n = len(jaxpr.invars)
    roles = list(invar_roles or [])
    roles += ["data"] * (n - len(roles))
    names = list(invar_names or [])
    names += [f"arg{i}" for i in range(len(names), n)]
    donated = set(int(i) for i in donate_argnums)

    vocab_extents = frozenset(
        int(v.aval.shape[0])
        for v, role, name in zip(jaxpr.invars, roles, names)
        if role == "param" and is_embedding_param(name)
        and hasattr(v, "aval") and len(getattr(v.aval, "shape", ())) >= 2
        and int(v.aval.shape[0]) >= VOCAB_SCALE_MIN_ROWS
    )
    walk = _LiveWalk(plan, axis_sizes, vocab_extents)

    invar_bytes: List[int] = []
    param_bytes = 0
    for i, (var, role, name) in enumerate(zip(jaxpr.invars, roles, names)):
        if not hasattr(var, "aval"):
            invar_bytes.append(0)
            continue
        if role == "param":
            nbytes = walk.param_bytes(name, var.aval)
            param_bytes += nbytes
        else:
            nbytes = walk.value_bytes(var.aval)
        invar_bytes.append(nbytes)
    freeable = [i in donated for i in range(n)]
    exempt = frozenset(v for v in jaxpr.outvars if _is_var(v))
    peak, temp_peak = walk.walk(jaxpr, invar_bytes, freeable, exempt)

    out_bytes = 0
    for v in jaxpr.outvars:
        if hasattr(v, "aval"):
            out_bytes += walk.value_bytes(v.aval)
    estimate = MemoryEstimate(
        peak_bytes=int(peak),
        argument_bytes=int(sum(invar_bytes)),
        output_bytes=int(out_bytes),
        param_bytes=int(param_bytes),
        temp_peak_bytes=int(temp_peak),
    )
    return estimate, walk.vocab_sites


def estimate_fn_memory(
    fn,
    *example_args,
    plan: Optional[ShardingPlan] = None,
    mesh: Optional[Any] = None,
    param_argnums: Sequence[int] = (),
    donate_argnums: Sequence[int] = (),
    axis_env: Optional[Sequence[Tuple[str, int]]] = None,
) -> MemoryEstimate:
    """Trace ``fn`` abstractly (no compile, no device) and estimate its
    per-device peak live bytes under ``plan``. ``param_argnums`` marks
    the state arguments (sized by their plan family; optimizer slots are
    just more param leaves, so the slot count is whatever the actual
    state pytree holds); ``donate_argnums`` marks arguments whose
    buffers the caller donates (freed at last use instead of resident
    to the end)."""
    import jax

    closed = jax.make_jaxpr(fn, axis_env=list(axis_env or ()))(*example_args)
    roles, names = _invar_names_roles(closed, example_args, param_argnums)
    # Map ARGUMENT donation to INVAR donation through the same flatten.
    donated_invars: List[int] = []
    donate_set = set(donate_argnums)
    idx = 0
    for i, arg in enumerate(example_args):
        n_leaves = len(jax.tree_util.tree_leaves(arg))
        if i in donate_set:
            donated_invars.extend(range(idx, idx + n_leaves))
        idx += n_leaves
    if idx != len(closed.jaxpr.invars):
        donated_invars = []
    estimate, _ = estimate_closed_jaxpr(
        closed, plan=plan, mesh=mesh, invar_roles=roles,
        invar_names=names, donate_argnums=donated_invars,
    )
    return estimate


def check_memory_fn(
    fn,
    *example_args,
    plan: Optional[ShardingPlan] = None,
    mesh: Optional[Any] = None,
    hbm_budget_bytes: Optional[int] = None,
    param_argnums: Sequence[int] = (),
    donate_argnums: Sequence[int] = (),
    program: str = "program",
    location: Optional[str] = None,
    axis_env: Optional[Sequence[Tuple[str, int]]] = None,
) -> List[Finding]:
    """The full pass-7 check over one live function: FML701 (budget),
    FML702 (vocab-scale intermediates), FML703 (undonated same-shape
    state updates)."""
    import jax

    closed = jax.make_jaxpr(fn, axis_env=list(axis_env or ()))(*example_args)
    roles, names = _invar_names_roles(closed, example_args, param_argnums)
    donate_set = set(donate_argnums)
    donated_invars: List[int] = []
    idx = 0
    for i, arg in enumerate(example_args):
        n_leaves = len(jax.tree_util.tree_leaves(arg))
        if i in donate_set:
            donated_invars.extend(range(idx, idx + n_leaves))
        idx += n_leaves
    if idx != len(closed.jaxpr.invars):
        donated_invars = []
    estimate, vocab_sites = estimate_closed_jaxpr(
        closed, plan=plan, mesh=mesh, invar_roles=roles,
        invar_names=names, donate_argnums=donated_invars,
    )
    findings: List[Finding] = []
    plan_name = (plan or REPLICATED).name

    if hbm_budget_bytes is not None and \
            estimate.peak_bytes > int(hbm_budget_bytes):
        findings.append(Finding(
            "FML701",
            f"program {program!r} under plan {plan_name!r}: estimated "
            f"{estimate.render()} exceeds the per-device HBM budget of "
            f"{human_bytes(hbm_budget_bytes)}",
            stage=program, location=location,
            fix_hint="shard further (a larger fsdp x tp product), drop "
                     "to a narrower quant tier (infer_plan's "
                     "quant_tiers= mode walks f32 -> bf16 -> int8), "
                     "donate the state buffers, or raise the budget",
        ))

    for prim, shape, dtype in vocab_sites:
        findings.append(Finding(
            "FML702",
            f"program {program!r}: {prim} materializes a vocab-scale "
            f"intermediate of shape {shape} ({dtype}) on the hot path — "
            "the embedding contract promises batch-sized payloads "
            "(lookup gathers rows, the gradient exchange moves "
            "batch-many rows), never a full-table value",
            stage=program, location=location,
            fix_hint="gather/scatter by ids instead of densifying "
                     "(one_hot @ table and full-table psum are the "
                     "shapes flinkml_tpu.embeddings exists to avoid)",
        ))

    # FML703 — same-shape state update without donation, at top level.
    donated = set(donated_invars)
    out_avals = [
        (tuple(v.aval.shape), str(v.aval.dtype))
        for v in closed.jaxpr.outvars if hasattr(v, "aval")
    ]
    flagged: set = set()
    for i, (var, role, name) in enumerate(
            zip(closed.jaxpr.invars, roles, names)):
        if role != "param" or i in donated or not hasattr(var, "aval"):
            continue
        shape = tuple(var.aval.shape)
        elems = 1
        for d in shape:
            elems *= int(d)
        if elems < DONATION_MIN_ELEMS or name in flagged:
            continue
        if (shape, str(var.aval.dtype)) in out_avals:
            flagged.add(name)
            findings.append(Finding(
                "FML703",
                f"program {program!r}: state leaf {name!r} "
                f"({shape}, {var.aval.dtype}) has a same-shape output "
                "(its update) but its input buffer is not donated — the "
                "old and new state coexist at the peak moment, doubling "
                "state memory",
                stage=program, column=name, location=location,
                fix_hint="pass donate_argnums for the state argument "
                         "(jax.jit(step, donate_argnums=(0,))) so XLA "
                         "writes the update in place",
            ))
    return findings


# ---------------------------------------------------------------------------
# FML704 — the tier ladder has no fitting rung
# ---------------------------------------------------------------------------


def check_tier_ladder(
    mesh,
    param_shapes: Mapping[str, Sequence[int]],
    hbm_budget_bytes: int,
    optimizer_slots: int = 1,
    tiers: Sequence[str] = QUANT_TIER_LADDER,
    location: Optional[str] = None,
) -> List[Finding]:
    """FML704 when no ``(plan, quant_tier)`` pair fits the budget — the
    finding carries :func:`~flinkml_tpu.sharding.plan.infer_plan`'s full
    per-tier footprint listing so the operator sees exactly how far off
    every rung of the ladder is."""
    try:
        infer_plan(
            mesh, param_shapes, hbm_budget_bytes,
            optimizer_slots=optimizer_slots, quant_tiers=tuple(tiers),
        )
    except NoFeasiblePlanError as e:
        return [Finding(
            "FML704",
            str(e),
            location=location,
            fix_hint="grow the mesh's fsdp/tp product, shrink the "
                     "vocab/model, or raise the per-device budget — "
                     "quantization alone cannot close this gap",
        )]
    return []


# ---------------------------------------------------------------------------
# Serving load-time gate
# ---------------------------------------------------------------------------


def estimate_serving_bytes(
    model: Any,
    schema: Mapping[str, Tuple[Any, Tuple[int, ...]]],
    max_batch_rows: int,
    policy: Optional[Any] = None,
) -> int:
    """A device-free upper-ish estimate of one serving replica's HBM
    footprint: every learned model array at the width the engine's
    precision tier actually stores it (int8 codes + scales under a
    ``quant`` policy, ``policy.compute`` under a mixed policy — the
    fused executor casts constants in-program), plus three live
    batch-sized buffers (input, one intermediate, output) at the
    largest dispatch bucket. The :class:`~flinkml_tpu.serving.engine
    .ServingEngine` load-time budget gate consumes this BEFORE the
    active-model flip, so a refused swap keeps the old model serving."""
    from flinkml_tpu.precision import quantizable, resolve_policy
    from flinkml_tpu.recovery.sentinel import _iter_stage_arrays

    policy = resolve_policy(policy)
    const_bytes = 0
    for _name, arr in _iter_stage_arrays(model):
        a = np.asarray(arr)
        if policy is not None and policy.quant == "int8" \
                and quantizable(a):
            cols = int(a.shape[-1]) if a.ndim >= 2 else 1
            const_bytes += a.size + 4 * cols
        elif policy is not None and policy.mixed:
            const_bytes += a.size * int(policy.compute_dtype.itemsize)
        else:
            const_bytes += int(a.nbytes)
    batch_bytes = 0
    for _col, (dtype, trailing) in schema.items():
        elems = int(max_batch_rows)
        for d in trailing:
            elems *= int(d)
        width = (
            int(policy.compute_dtype.itemsize)
            if policy is not None and policy.mixed
            else _dtype_itemsize(dtype)
        )
        batch_bytes += elems * width
    return int(const_bytes + 3 * batch_bytes)


# ---------------------------------------------------------------------------
# *.memory.json fixtures / configs
# ---------------------------------------------------------------------------


def _probe_program(spec: Mapping):
    """Build the probe named by ``spec`` — ``(fn, example_args,
    param_argnums, donate_argnums)``. The trainer probes are the REAL
    in-repo step builders (the ``*.policy.json`` precedent), so a
    fixture exercises the same jaxpr the product compiles.

    ``sgd_step``/``adam_step``: :func:`~flinkml_tpu.sharding.apply
    .linear_step_fn` over the real optimizer state (``donate`` declares
    whether the state buffer is donated — ``false`` is the FML703
    shape). ``embedding_lookup``: the batch-sized contract (clean).
    ``embedding_dense_grad``: the one-hot densified gradient — the
    FML702 shape.
    """
    import jax
    import jax.numpy as jnp

    name = str(spec.get("name", ""))
    dim = int(spec.get("dim", 8))
    rows = int(spec.get("rows", 8))
    dtype = np.dtype(str(spec.get("dtype", "float32")))

    if name in ("sgd_step", "adam_step"):
        from flinkml_tpu.sharding.apply import (
            init_linear_state,
            linear_step_fn,
        )

        optimizer = "sgd" if name == "sgd_step" else "adam"
        step = linear_step_fn(
            loss=str(spec.get("loss", "logistic")), optimizer=optimizer,
            dtype_name=dtype.name, learning_rate=0.1, momentum=0.9,
            reg_l2=0.0, reg_l1=0.0, policy=None,
        )
        state = init_linear_state(dim, optimizer, dtype)
        batch = jax.ShapeDtypeStruct((rows, dim), dtype)
        vec = jax.ShapeDtypeStruct((rows,), dtype)
        donate = (0,) if bool(spec.get("donate", False)) else ()
        return step, (state, batch, vec, vec), (0,), donate
    if name == "embedding_lookup":
        vocab = int(spec.get("vocab", 4096))

        def lookup(state, ids):
            return jnp.take(state["emb/embedding"], ids, axis=0)

        table = jax.ShapeDtypeStruct((vocab, dim), dtype)
        ids = jax.ShapeDtypeStruct((rows,), np.int32)
        return lookup, ({"emb/embedding": table}, ids), (0,), ()
    if name == "embedding_dense_grad":
        vocab = int(spec.get("vocab", 4096))

        def dense_grad(state, ids, grad):
            table = state["emb/embedding"]
            onehot = jax.nn.one_hot(ids, table.shape[0],
                                    dtype=table.dtype)
            return {"emb/embedding": table + onehot.T @ grad}

        table = jax.ShapeDtypeStruct((vocab, dim), dtype)
        ids = jax.ShapeDtypeStruct((rows,), np.int32)
        grad = jax.ShapeDtypeStruct((rows, dim), dtype)
        return dense_grad, ({"emb/embedding": table}, ids, grad), (0,), ()
    raise ValueError(
        f"unknown memory probe program {name!r} (known: sgd_step, "
        "adam_step, embedding_lookup, embedding_dense_grad)"
    )


def _resolve_plan(raw) -> ShardingPlan:
    if raw is None:
        return REPLICATED
    if isinstance(raw, str):
        try:
            return PRESETS[raw]
        except KeyError:
            raise ValueError(
                f"unknown plan preset {raw!r} (presets: {sorted(PRESETS)})"
            ) from None
    return ShardingPlan.from_json_dict(raw)


def check_memory_file(path: str) -> List[Finding]:
    """Validate a ``*.memory.json`` fixture/config:

    .. code-block:: json

        {"mesh": {"data": 1, "fsdp": 4, "tp": 2},
         "plan": "embedding",
         "hbm_budget_bytes": 1048576,
         "program": {"name": "sgd_step", "dim": 65536, "rows": 64,
                     "donate": false},
         "param_shapes": {"emb/embedding": [1048576, 64]},
         "optimizer_slots": 1,
         "tiers": ["float32", "bfloat16", "int8"]}

    ``program`` (optional) names a probe traced under the plan and
    checked for FML701/702/703 against the budget; ``tiers`` (optional,
    with ``param_shapes``) walks the quant ladder and reports FML704
    when no tier fits. ``plan`` is a preset name or a full plan object.
    Unreadable or malformed files report one FML701 finding naming the
    path — the gate must fail loudly, not skip silently.
    """
    try:
        with open(path, "r") as fh:
            doc = json.load(fh)
        plan = _resolve_plan(doc.get("plan"))
        mesh = {str(k): int(v) for k, v in (doc.get("mesh") or {}).items()}
        budget = doc.get("hbm_budget_bytes")
        program = doc.get("program")
        shapes = {
            str(k): tuple(int(d) for d in v)
            for k, v in (doc.get("param_shapes") or {}).items()
        }
        slots = int(doc.get("optimizer_slots", 1))
        tiers = doc.get("tiers")
        if program is None and tiers is None:
            raise ValueError(
                "a *.memory.json target needs a 'program' probe, a "
                "'tiers' ladder check, or both"
            )
    except (OSError, ValueError, KeyError, TypeError) as e:
        return [Finding(
            "FML701",
            f"memory file {path} is unreadable or malformed: {e!r}",
            location=path,
            fix_hint="see docs/development/static_analysis.md for the "
                     "*.memory.json schema",
        )]
    findings: List[Finding] = []
    if program is not None:
        try:
            fn, args, param_argnums, donate = _probe_program(program)
            findings.extend(check_memory_fn(
                fn, *args, plan=plan, mesh=mesh,
                hbm_budget_bytes=budget, param_argnums=param_argnums,
                donate_argnums=donate,
                program=str(program.get("name")), location=path,
            ))
        except (ValueError, TypeError) as e:
            return [Finding(
                "FML701",
                f"memory file {path} names a bad probe program: {e}",
                location=path,
                fix_hint="see docs/development/static_analysis.md",
            )]
    if tiers is not None and shapes and budget is not None:
        findings.extend(check_tier_ladder(
            mesh, shapes, int(budget), optimizer_slots=slots,
            tiers=[str(t) for t in tiers], location=path,
        ))
    return findings
