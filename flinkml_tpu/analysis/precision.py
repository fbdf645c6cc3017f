"""Pass 5 — precision-flow validation (FML6xx), before any compile.

A :class:`~flinkml_tpu.precision.PrecisionPolicy` is a promise about
where a program is allowed to round: ``compute`` is where the hot work
runs (bf16 on TPU), ``accum`` is the floor under every accumulation,
``params`` is the storage width of parameters and optimizer state. This
pass abstract-interprets jaxprs — device-free, recursing through
jit/scan/while/cond exactly like the collective extractor
(:mod:`flinkml_tpu.analysis.collectives`) — tracking per-value **dtype
provenance** against the declared policy:

  - **FML601** — a reduction/accumulation (``reduce_sum``/``cumsum``, a
    ``dot_general`` accumulator, an optimizer moment/parameter update —
    any add/mul chain still carrying parameter-or-carry provenance)
    runs in a dtype narrower than ``policy.accum``. bf16 accumulation is
    THE silent-corruption shape mixed precision must not introduce.
  - **FML602** — a silent upcast inside the compute region: a stray
    strong-typed f32/f64 constant promotes a ``policy.compute``-width
    value wider, defeating exactly the bandwidth/MXU savings the policy
    declared (the mirror of FML106's f64 promotion, policy-scoped).
  - **FML603** — a parameter or optimizer-state leaf is *stored*
    narrower than ``policy.params`` (bf16 master weights: each step
    rounds the state, divergence compounds).
  - **FML604** — a cross-rank collective (psum/all-gather/...) operates
    on a dtype narrower than ``accum`` without an explicit pre-cast:
    reduction order across ranks is already nondeterministic, doing it
    in bf16 compounds rounding with topology. An explicit narrowing
    cast immediately before the collective (the deliberate
    bandwidth-for-precision trade) is allowed.
  - **FML605** — policy/plan conflict: a
    :class:`~flinkml_tpu.sharding.plan.ShardingPlan` whose HBM-budget
    math (``infer_plan``/FML503 ``dtype_bytes``) assumed a different
    parameter width than the policy declares — the budget that
    "fit" was computed for a model that will not exist.

**Provenance rules.** Input leaves are labeled ``param`` (parameters +
optimizer state) or ``data`` (batches); literals/constvars are
``const``; scan/while carries gain ``carry``. Provenance flows through
every eqn — EXCEPT through a *narrowing* ``convert_element_type``,
which resets to ``data``: casting a parameter down to ``compute`` at a
step boundary is the sanctioned contract (SNIPPETS.md [3]'s
``to_bf16``), and everything derived from the cast is compute-region
work, not state math. Anything still carrying ``param``/``carry``
provenance at a narrow width therefore IS state math running narrow.

Inputs come from live functions (:func:`check_precision_fn` — what the
fused executor, the plan trainers, and serving call pre-compile) or
from ``*.policy.json`` fixtures (:func:`check_policy_file` — what the
CLI and the CI fixture gate consume). The same dtype-flow walk also
backs the FML106 silent-f64-promotion check
(:func:`promotion_findings`), so single-stage and fused multi-stage
programs share one code path. See ``docs/development/precision.md``.
"""

from __future__ import annotations

import json
from typing import (
    Any,
    Dict,
    Iterable,
    List,
    Mapping,
    Optional,
    Sequence,
    Tuple,
)

import numpy as np

from flinkml_tpu.analysis.collectives import collective_name
from flinkml_tpu.analysis.findings import Finding
from flinkml_tpu.precision import (
    PrecisionPolicy,
    is_narrower,
    significand_bits,
)

#: Primitives that reduce/accumulate across elements — their output
#: dtype IS their accumulator dtype.
REDUCTION_PRIMITIVES = frozenset({
    "reduce_sum", "reduce_prod", "cumsum", "cumprod", "cumlogsumexp",
    "add_any",
})

#: Elementwise arithmetic that, when still carrying param/carry
#: provenance at a narrow width, is a state/accumulator update.
_UPDATE_PRIMITIVES = frozenset({"add", "sub", "mul", "div", "add_any"})

#: Binary arithmetic checked for the stray-wide-constant promotion shape.
_PROMOTION_PRIMITIVES = frozenset({
    "add", "sub", "mul", "div", "max", "min", "pow", "atan2", "rem",
})

_PARAMISH = frozenset({"param", "carry"})


def _is_float(dtype) -> bool:
    try:
        return np.dtype(dtype).kind == "f" or "bfloat16" in str(dtype)
    except TypeError:
        return False


def _is_int8(dtype) -> bool:
    try:
        return np.dtype(dtype) == np.dtype(np.int8)
    except TypeError:
        return False


def _is_integer(dtype) -> bool:
    try:
        return np.dtype(dtype).kind in "iu"
    except TypeError:
        return False


def _bits(dtype) -> int:
    return significand_bits(dtype)


class _Flow:
    """One dtype-provenance walk over a closed jaxpr (and its
    sub-jaxprs), accumulating FML601/602/604 findings."""

    def __init__(self, policy: PrecisionPolicy, program: str,
                 location: Optional[str]):
        self.policy = policy
        self.program = program
        self.location = location
        self.findings: List[Finding] = []
        self._seen: set = set()
        # var -> provenance frozenset; vars absent (constvars) are const.
        self.prov: Dict[Any, frozenset] = {}
        # var -> significand bits it was widened FROM / narrowed FROM by
        # a convert_element_type (for the FML602/FML604 shapes).
        self.widened_from: Dict[Any, int] = {}
        self.narrowed_from: Dict[Any, int] = {}

    # -- provenance helpers ------------------------------------------------
    @staticmethod
    def _is_var(atom) -> bool:
        # Literals are unhashable in some jax versions — never dict keys.
        return hasattr(atom, "aval") and type(atom).__name__ != "Literal"

    def prov_of(self, atom) -> frozenset:
        if not self._is_var(atom):
            return frozenset({"const"})
        return self.prov.get(atom, frozenset({"const"}))

    def _widened_from(self, atom) -> int:
        return self.widened_from.get(atom, 0) if self._is_var(atom) else 0

    def _narrowed_from(self, atom) -> int:
        return self.narrowed_from.get(atom, 0) if self._is_var(atom) else 0

    def _dtype(self, atom):
        return atom.aval.dtype if hasattr(atom, "aval") else np.dtype(
            np.asarray(atom).dtype)

    def _add(self, rule: str, key: tuple, message: str, fix: str,
             column: Optional[str] = None) -> None:
        if key in self._seen:
            return
        self._seen.add(key)
        self.findings.append(Finding(
            rule, message, stage=self.program, column=column,
            location=self.location, fix_hint=fix,
        ))

    # -- the walk ----------------------------------------------------------
    def walk(self, jaxpr, invar_prov: Sequence[frozenset]) -> List[frozenset]:
        """Walk one (open) jaxpr with the given per-invar provenance;
        returns per-outvar provenance."""
        for var, p in zip(jaxpr.invars, invar_prov):
            self.prov[var] = p
        for eqn in jaxpr.eqns:
            self._eqn(eqn)
        return [self.prov_of(v) for v in jaxpr.outvars]

    def _eqn(self, eqn) -> None:
        name = eqn.primitive.name
        in_provs = [self.prov_of(a) for a in eqn.invars]
        joined = frozenset().union(*in_provs) if in_provs else frozenset()

        if name == "convert_element_type":
            self._convert(eqn, joined)
            return
        if self._recurse(eqn, name, in_provs, joined):
            return

        accum_bits = _bits(self.policy.accum)
        out = eqn.outvars[0] if eqn.outvars else None
        out_dt = out.aval.dtype if out is not None and hasattr(out, "aval") \
            else None
        out_is_float = out_dt is not None and _is_float(out_dt)

        # FML604 — narrow cross-rank collective without explicit pre-cast.
        if collective_name(name) is not None:
            for a in eqn.invars:
                dt = self._dtype(a)
                if not _is_float(dt) or _bits(dt) >= accum_bits:
                    continue
                if self._narrowed_from(a) >= accum_bits:
                    continue  # deliberate bandwidth cast right before
                self._add(
                    "FML604", ("FML604", name, str(dt)),
                    f"collective {name!r} operates on {dt} — narrower "
                    f"than policy.accum ({self.policy.accum}) — without "
                    "an explicit pre-cast; cross-rank reduction order is "
                    "already nondeterministic, rounding it at "
                    f"{dt} compounds with topology",
                    fix="accumulate collectives at policy.accum, or cast "
                        "down EXPLICITLY right before the collective to "
                        "declare the bandwidth-for-precision trade",
                )

        # FML606 — quantized params accumulated at integer width. The
        # int8 tier's contract is dequant-THEN-accumulate: a reduction
        # or dot accumulator whose operands include int8 param/carry
        # state and whose output is still integer ran the accumulation
        # unscaled — an int8 accumulator wraps at ±127, and even a
        # widened int32 sum is missing its per-column scales (the values
        # are dimensionless codes until multiplied by scale).
        if (
            (name in REDUCTION_PRIMITIVES or name == "dot_general")
            and out_dt is not None and _is_integer(out_dt)
            and (joined & _PARAMISH)
            and any(_is_int8(self._dtype(a)) for a in eqn.invars)
        ):
            self._add(
                "FML606", ("FML606", name, str(out_dt)),
                f"{name} accumulates int8-quantized parameters at "
                f"{out_dt} without a dequant scale — int8 accumulation "
                "wraps at ±127, and unscaled integer codes are not "
                "values",
                fix="dequantize first (q.astype(policy.compute) * scale, "
                    "the sanctioned int8-tier shape — "
                    "flinkml_tpu.precision.quantize_absmax) so the "
                    "accumulation runs at policy.accum on scaled floats",
            )

        # FML601(a/b) — reductions and dot accumulators.
        if out_is_float and _bits(out_dt) < accum_bits:
            if name in REDUCTION_PRIMITIVES:
                self._add(
                    "FML601", ("FML601", name, str(out_dt)),
                    f"{name} accumulates in {out_dt}, narrower than "
                    f"policy.accum ({self.policy.accum})",
                    fix="cast the operand up before reducing (or use "
                        "preferred_element_type) so the running sum "
                        "carries policy.accum precision",
                )
            elif name == "dot_general":
                self._add(
                    "FML601", ("FML601", name, str(out_dt)),
                    f"dot_general accumulator runs at {out_dt}, narrower "
                    f"than policy.accum ({self.policy.accum})",
                    fix="pass preferred_element_type=policy.accum to the "
                        "matmul so the MXU/accumulator output carries "
                        "full precision (inputs may stay at "
                        "policy.compute)",
                )
            # FML601(c) — state/accumulator update still carrying
            # param/carry provenance at a narrow width.
            elif name in _UPDATE_PRIMITIVES and (joined & _PARAMISH):
                self._add(
                    "FML601", ("FML601", "update", name, str(out_dt)),
                    f"parameter/optimizer-state update ({name}) runs at "
                    f"{out_dt}, narrower than policy.accum "
                    f"({self.policy.accum}) — each step rounds the "
                    "state, divergence compounds",
                    fix="store state at policy.params, cast to "
                        "policy.compute at the step boundary for the "
                        "forward work, and run every state update at "
                        "policy.accum",
                )

        # FML602 — stray wide constant promotes the compute region.
        if (out_is_float and name in _PROMOTION_PRIMITIVES
                and _bits(out_dt) > _bits(self.policy.compute)):
            compute_bits = _bits(self.policy.compute)
            has_widened = any(
                self._widened_from(a) == compute_bits for a in eqn.invars
            )
            wide_const = any(
                self.prov_of(a) <= frozenset({"const"})
                and _is_float(self._dtype(a))
                and _bits(self._dtype(a)) > compute_bits
                for a in eqn.invars
            )
            if has_widened and wide_const:
                self._add(
                    "FML602", ("FML602", name, str(out_dt)),
                    f"a strong-typed {out_dt} constant promotes a "
                    f"{self.policy.compute} value to {out_dt} inside the "
                    f"compute region ({name}) — the whole downstream "
                    "chain runs wide, defeating the bf16 savings the "
                    "policy declared",
                    fix="make the constant weak-typed (a python scalar) "
                        "or cast it to policy.compute; promotion against "
                        "strong constants is silent",
                )

        for ov in eqn.outvars:
            self.prov[ov] = joined

    def _convert(self, eqn, joined: frozenset) -> None:
        (a,) = eqn.invars
        (out,) = eqn.outvars
        in_dt, out_dt = self._dtype(a), out.aval.dtype
        if _is_float(in_dt) and _is_float(out_dt):
            if _bits(out_dt) < _bits(in_dt):
                # Sanctioned step-boundary down-cast: drop param/carry
                # taint — downstream is compute-region work.
                self.narrowed_from[out] = _bits(in_dt)
                self.prov[out] = frozenset({"data"})
                return
            if _bits(out_dt) > _bits(in_dt):
                self.widened_from[out] = _bits(in_dt)
        self.prov[out] = joined

    def _recurse(self, eqn, name: str, in_provs: List[frozenset],
                 joined: frozenset) -> bool:
        """Walk sub-jaxprs of control-flow/call primitives, mapping
        operand provenance onto their invars (scan/while carries gain
        the ``carry`` tag). Returns True when handled."""
        params = eqn.params
        if name == "scan":
            closed = params["jaxpr"]
            nc, ncar = params["num_consts"], params["num_carry"]
            inner = list(in_provs)
            for i in range(nc, nc + ncar):
                if i < len(inner):
                    inner[i] = inner[i] | {"carry"}
            out_provs = self.walk(closed.jaxpr, inner)
        elif name == "while":
            body = params["body_jaxpr"]
            bn = params["body_nconsts"]
            cn = params["cond_nconsts"]
            carry_provs = [p | {"carry"} for p in in_provs[cn + bn:]]
            self.walk(params["cond_jaxpr"].jaxpr,
                      in_provs[:cn] + carry_provs)
            out_provs = self.walk(body.jaxpr,
                                  in_provs[cn:cn + bn] + carry_provs)
        elif name == "cond":
            branches = params["branches"]
            out_provs = None
            for br in branches:
                provs = self.walk(br.jaxpr, in_provs[1:])
                out_provs = provs if out_provs is None else [
                    a | b for a, b in zip(out_provs, provs)
                ]
            out_provs = out_provs or []
        elif "jaxpr" in params and hasattr(
                getattr(params["jaxpr"], "jaxpr", None), "eqns"):
            # jit / closed_call / checkpoint-style wrappers.
            out_provs = self.walk(params["jaxpr"].jaxpr, in_provs)
        elif "call_jaxpr" in params:
            cj = params["call_jaxpr"]
            out_provs = self.walk(getattr(cj, "jaxpr", cj), in_provs)
        else:
            return False
        for ov, p in zip(eqn.outvars, out_provs):
            self.prov[ov] = p
        return True


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def _path_name(path) -> str:
    parts = []
    for k in path:
        for attr in ("key", "name", "idx"):
            if hasattr(k, attr):
                parts.append(str(getattr(k, attr)))
                break
        else:
            parts.append(str(k))
    return "/".join(parts)


def check_closed_jaxpr(
    closed,
    policy: PrecisionPolicy,
    invar_roles: Optional[Sequence[str]] = None,
    invar_names: Optional[Sequence[str]] = None,
    program: str = "program",
    location: Optional[str] = None,
) -> List[Finding]:
    """FML601/602/603/604 over one closed jaxpr. ``invar_roles`` labels
    each invar ``"param"`` or ``"data"`` (default: all data);
    ``invar_names`` names the leaves for FML603 messages."""
    jaxpr = closed.jaxpr
    roles = list(invar_roles or ())
    roles += ["data"] * (len(jaxpr.invars) - len(roles))
    names = list(invar_names or ())
    names += [f"arg{i}" for i in range(len(names), len(jaxpr.invars))]

    flow = _Flow(policy, program, location)
    params_bits = _bits(policy.params)
    quant = getattr(policy, "quant", None)
    for var, role, name in zip(jaxpr.invars, roles, names):
        dt = var.aval.dtype
        if role == "param" and _is_float(dt) and _bits(dt) < params_bits:
            flow._add(
                "FML603", ("FML603", name),
                f"parameter/optimizer-state leaf {name!r} is stored as "
                f"{dt}, narrower than policy.params ({policy.params})",
                fix="keep master weights and optimizer moments at "
                    "policy.params; cast to policy.compute only at the "
                    "step boundary (to_bf16/to_fp32)",
                column=name,
            )
        # FML607 — int8-quantized params under a policy that never
        # declared quantization: the values are absmax-degraded codes,
        # and serving them as the full-width tier republishes the
        # quality loss without the policy paper trail.
        if role == "param" and _is_int8(dt) and quant is None:
            flow._add(
                "FML607", ("FML607", name),
                f"parameter leaf {name!r} is stored as int8 but policy "
                f"{policy.name!r} declares no quantization scheme — "
                "quantized params are republished as the full-width "
                f"({policy.params}) tier",
                fix="serve quantized models under the int8 tier "
                    "(PrecisionPolicy quant='int8', preset "
                    "'int8_inference') or republish the full-width "
                    "master weights",
                column=name,
            )
    flow.walk(
        jaxpr,
        [frozenset({r}) for r in roles[:len(jaxpr.invars)]],
    )
    return flow.findings


def check_precision_fn(
    fn,
    *example_args,
    policy: PrecisionPolicy,
    param_argnums: Iterable[int] = (),
    program: str = "program",
    location: Optional[str] = None,
    axis_env: Optional[Sequence[Tuple[str, int]]] = None,
) -> List[Finding]:
    """Trace ``fn`` abstractly (shapes/dtypes only — no compile, no
    device) and run the precision-flow pass. ``param_argnums`` marks
    which positional arguments hold parameters/optimizer state (their
    leaves are checked against ``policy.params`` and taint the update
    chain for FML601)."""
    import jax

    closed = jax.make_jaxpr(fn, axis_env=list(axis_env or ()))(*example_args)
    param_set = set(param_argnums)
    roles: List[str] = []
    names: List[str] = []
    for i, arg in enumerate(example_args):
        leaves_with_paths, _ = jax.tree_util.tree_flatten_with_path(arg)
        role = "param" if i in param_set else "data"
        for path, _leaf in leaves_with_paths:
            roles.append(role)
            names.append(_path_name(path) or f"arg{i}")
    if len(roles) != len(closed.jaxpr.invars):
        # Structure mismatch (kwargs, donated args, ...): fall back to
        # unlabeled flow — FML601/602/604 still run, FML603 cannot.
        roles, names = [], []
    return check_closed_jaxpr(
        closed, policy, invar_roles=roles, invar_names=names,
        program=program, location=location,
    )


def validate_precision(
    fn,
    *example_args,
    policy: PrecisionPolicy,
    param_argnums: Iterable[int] = (),
    program: str = "program",
    location: Optional[str] = None,
    axis_env=None,
    extra_findings: Iterable[Finding] = (),
) -> None:
    """Run the pass and raise the typed
    :class:`~flinkml_tpu.precision.PrecisionValidationError` on any
    error-severity finding — the pre-compile gate every policy-threaded
    entry point calls (the FML5xx ``PlanValidationError`` shape)."""
    from flinkml_tpu.precision import PrecisionValidationError

    findings = list(extra_findings) + check_precision_fn(
        fn, *example_args, policy=policy, param_argnums=param_argnums,
        program=program, location=location, axis_env=axis_env,
    )
    errors = [f for f in findings if f.severity == "error"]
    if errors:
        raise PrecisionValidationError(
            f"program {program!r} failed precision-flow validation "
            f"against policy {policy.describe()}:\n"
            + "\n".join(f.render() for f in errors),
            findings=errors,
        )


# ---------------------------------------------------------------------------
# FML605 — policy / sharding-plan conflict
# ---------------------------------------------------------------------------


def check_policy_plan(
    policy: PrecisionPolicy,
    dtype_bytes: Optional[int] = None,
    plan_name: Optional[str] = None,
    location: Optional[str] = None,
) -> List[Finding]:
    """FML605 when a plan's HBM-budget math assumed a parameter width
    different from ``policy.params``. ``dtype_bytes`` is the width the
    plan validation (``infer_plan``/FML503) used."""
    if dtype_bytes is None:
        return []
    want = int(policy.params_dtype.itemsize)
    if int(dtype_bytes) == want:
        return []
    label = f"plan {plan_name!r}" if plan_name else "the sharding plan"
    return [Finding(
        "FML605",
        f"{label} budgets parameters at {int(dtype_bytes)} B/elem but the "
        f"policy stores params as {policy.params} ({want} B/elem) — the "
        "HBM footprint the plan validated is not the footprint that will "
        "exist",
        stage=plan_name, location=location,
        fix_hint="validate the plan with dtype_bytes = "
                 "np.dtype(policy.params).itemsize (and re-run infer_plan "
                 "— a budget that fit at 2 B may not fit at 4 B)",
    )]


# ---------------------------------------------------------------------------
# *.policy.json fixtures / configs
# ---------------------------------------------------------------------------


def _example_program(spec: Mapping):
    """Build a named example program for a policy file: ``(fn,
    example_args, param_argnums, axis_env)``. The trainer programs are
    the REAL in-repo step builders, so a fixture exercises the same
    jaxpr the product compiles."""
    import jax

    name = str(spec.get("name", ""))
    dim = int(spec.get("dim", 8))
    rows = int(spec.get("rows", 8))
    dtype = np.dtype(spec.get("dtype", "float32")) if \
        spec.get("dtype") != "bfloat16" else _bf16()

    if name in ("sgd_step", "adam_step"):
        from flinkml_tpu.sharding.apply import (
            init_linear_state,
            linear_step_fn,
        )

        optimizer = "sgd" if name == "sgd_step" else "adam"
        step = linear_step_fn(
            loss=str(spec.get("loss", "logistic")), optimizer=optimizer,
            dtype_name=np.dtype(dtype).name, learning_rate=0.1,
            momentum=0.9, reg_l2=0.0, reg_l1=0.0, policy=None,
        )
        state = init_linear_state(dim, optimizer, dtype)
        batch = jax.ShapeDtypeStruct((rows, dim), dtype)
        vec = jax.ShapeDtypeStruct((rows,), dtype)
        return step, (state, batch, vec, vec), (0,), None
    if name == "stray_constant_chain":
        const = np.float32(float(spec.get("constant", 1.5)))

        def chain(x):
            return x * const

        return chain, (jax.ShapeDtypeStruct((rows, dim), dtype),), (), None
    if name == "state_passthrough":
        # Pure identity: the ONLY thing checkable is how the state is
        # STORED (the invar dtypes) — isolates FML603 from FML601.
        def ident(state):
            return state

        state = {"coef": jax.ShapeDtypeStruct((dim,), dtype),
                 "momentum": jax.ShapeDtypeStruct((dim,), dtype)}
        return ident, (state,), (0,), None
    if name == "psum_gradient":
        axis = str(spec.get("axis", "data"))

        def grad_sync(g):
            return jax.lax.psum(g, axis)

        return (grad_sync, (jax.ShapeDtypeStruct((dim,), dtype),), (),
                [(axis, int(spec.get("axis_size", 8)))])
    if name == "int8_unscaled_matmul":
        # The FML606 shape: int8-quantized weights matmul'd while still
        # integer codes — the accumulator wraps and the scales never
        # apply. The good twin dequantizes first (see
        # docs/development/precision.md).
        import jax.numpy as jnp

        def unscaled(q, x):
            return jnp.dot(x, q)

        q = jax.ShapeDtypeStruct((dim, dim), np.int8)
        x = jax.ShapeDtypeStruct((rows, dim), np.int8)
        return unscaled, (q, x), (0,), None
    if name == "int8_state_passthrough":
        # The FML607 shape: int8-STORED params under whatever policy the
        # file declares — flagged unless the policy declares quant.
        def ident(state):
            return state

        state = {"coef_q": jax.ShapeDtypeStruct((dim, dim), np.int8),
                 "coef_scale": jax.ShapeDtypeStruct((dim,), np.float32)}
        return ident, (state,), (0,), None
    raise ValueError(
        f"unknown example program {name!r} (known: sgd_step, adam_step, "
        "stray_constant_chain, state_passthrough, psum_gradient, "
        "int8_unscaled_matmul, int8_state_passthrough)"
    )


def _bf16():
    import ml_dtypes

    return np.dtype(ml_dtypes.bfloat16)


def check_policy_file(path: str) -> List[Finding]:
    """Validate a ``*.policy.json`` fixture/config:

    .. code-block:: json

        {"policy": {"name": "mixed", "compute": "bfloat16",
                    "accum": "float32", "params": "float32"},
         "program": {"name": "sgd_step", "dim": 8, "dtype": "bfloat16"},
         "plan": {"name": "fsdp", "dtype_bytes": 2}}

    ``program`` (optional) names an example program traced against the
    policy (FML601-604); ``plan`` (optional) supplies the width the
    plan's HBM math used (FML605). Unreadable or malformed files report
    one FML601 finding naming the path — the gate must fail loudly,
    not skip silently.
    """
    try:
        with open(path, "r") as fh:
            doc = json.load(fh)
        policy = PrecisionPolicy.from_json_dict(doc["policy"])
        program = doc.get("program")
        plan = doc.get("plan") or {}
    except (OSError, ValueError, KeyError, TypeError) as e:
        return [Finding(
            "FML601",
            f"precision-policy file {path} is unreadable or malformed: "
            f"{e!r}",
            location=path,
            fix_hint="see docs/development/precision.md for the "
                     "*.policy.json schema",
        )]
    findings: List[Finding] = []
    if program is not None:
        # The guard spans the TRACE too: example programs validate some
        # fields only when traced (e.g. the loss name inside the step),
        # and a trace-time error must become this file's one finding,
        # not a traceback that aborts the run with later targets
        # unchecked.
        try:
            fn, args, param_argnums, axis_env = _example_program(program)
            file_findings = check_precision_fn(
                fn, *args, policy=policy, param_argnums=param_argnums,
                program=str(program.get("name")), location=path,
                axis_env=axis_env,
            )
        except (ValueError, TypeError) as e:
            return [Finding(
                "FML601",
                f"precision-policy file {path} names a bad program: {e}",
                location=path,
                fix_hint="see docs/development/precision.md",
            )]
        findings.extend(file_findings)
    findings.extend(check_policy_plan(
        policy,
        dtype_bytes=plan.get("dtype_bytes"),
        plan_name=plan.get("name"),
        location=path,
    ))
    return findings


# ---------------------------------------------------------------------------
# FML106 — silent f64 promotion, through the same dtype-flow walk
# ---------------------------------------------------------------------------

_WIDE = np.dtype(np.float64)


def _widening_sites(jaxpr, out: List[str]) -> None:
    """Primitive names of eqns that produce float64 from all-narrower
    float operands — the exact point a silent promotion happens
    (recursive over sub-jaxprs)."""
    for eqn in jaxpr.eqns:
        outs = [v.aval.dtype for v in eqn.outvars if hasattr(v, "aval")]
        if any(np.dtype(d) == _WIDE for d in outs if _is_float(d)):
            in_floats = [
                np.dtype(a.aval.dtype) for a in eqn.invars
                if hasattr(a, "aval") and _is_float(a.aval.dtype)
            ]
            if in_floats and all(d != _WIDE for d in in_floats):
                out.append(eqn.primitive.name)
        for v in eqn.params.values():
            _walk_widening_param(v, out)


def _walk_widening_param(v: Any, out: List[str]) -> None:
    if hasattr(v, "eqns"):
        _widening_sites(v, out)
    elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
        _widening_sites(v.jaxpr, out)
    elif isinstance(v, (tuple, list)):
        for item in v:
            _walk_widening_param(item, out)


def promotion_findings(
    closed,
    input_dtypes: Sequence,
    output_dtypes: Mapping[str, Any],
    stage: Optional[str] = None,
    location: Optional[str] = None,
) -> List[Finding]:
    """FML106 over one (possibly fused multi-stage) program jaxpr: every
    known float input is narrow but an output came back float64 — the
    widening happened inside, silently. The ONE code path behind both
    the per-stage validator check and the fused-run check. ``closed``
    (the jaxpr that localizes the first widening primitive for the
    message) may be a zero-arg CALLABLE — it is only invoked once a
    finding is certain, so the clean-pipeline common case never pays a
    trace for localization."""
    known_in = [np.dtype(d) for d in input_dtypes if d is not None]
    # Any non-float or already-wide known input legitimizes a float64
    # output (int64→float conversion gives f64 under x64) — bail, same
    # as the validator's original per-stage check.
    if not known_in or any(not _is_float(d) or d == _WIDE
                           for d in known_in):
        return []
    wide_outs = [
        name for name, d in output_dtypes.items()
        if d is not None and np.dtype(d) == _WIDE
    ]
    if not wide_outs:
        return []
    sites: List[str] = []
    if callable(closed):
        closed = closed()
    if closed is not None:
        _widening_sites(closed.jaxpr, sites)
    at = f" (widened at {sites[0]!r})" if sites else ""
    ins = ", ".join(sorted({str(d) for d in known_in}))
    return [Finding(
        "FML106",
        f"inputs are {ins} but output {name!r} is float64 "
        f"(silent promotion){at}",
        stage=stage, column=name, location=location,
        fix_hint="cast explicitly or preserve the input dtype; float64 "
                 "on the CPU fallback path doubles bandwidth and memory",
    ) for name in wide_outs]
