"""Randomized chaos soak: sampled fault schedules, invariants, shrink.

Hand-scripted fault plans only cover the interleavings someone thought
to write down. The soak samples schedules across the fault seams
(:class:`flinkml_tpu.faults.FuzzPlan` — deterministic in ``(seed,
index)``), runs a real online trainer under each one with the
self-healing machinery armed, restarts it on scripted crashes exactly
like an orchestrator would, and asserts the recovery INVARIANTS:

1. **finite** — the final model holds no non-finite value;
2. **no silent fresh start / no mis-versioned model** — the model
   version equals ``batches - quarantined`` (a resume that silently
   restarted, or a poisoned batch that silently counted, both break
   this);
3. **parity** — the final coefficients are bit-identical to the same
   stream trained WITHOUT the quarantined batches (the golden run);
4. **ledger consistent** — the quarantine ledger names exactly the
   batches the schedule's numerics faults poisoned, nothing else.

A failing schedule is **shrunk** to a minimal reproducer (greedy
delta-debugging over the fault list: drop every fault whose removal
keeps the failure) and written as a deterministic
:class:`~flinkml_tpu.faults.FaultPlan` JSON artifact
(:func:`flinkml_tpu.faults.plan_to_json`) that
:func:`flinkml_tpu.faults.plan_from_json` replays exactly.

Tier-1 runs a fixed-seed soak of 25 schedules
(``tests/test_recovery.py::test_chaos_soak_small_budget_green``) and a
shrink demonstration on a seeded failing schedule
(``::test_shrink_minimizes_to_the_poison``). Run it by hand::

    JAX_PLATFORMS=cpu python -m flinkml_tpu.recovery.fuzz \
        --seed 7 --budget 25 --repro-dir /tmp/repros

**Serving soak** (``--serving``): the same sample→run→shrink loop
pointed at the serving pool's gray-failure seams instead of the trainer
loop. Each schedule draws 1–3 faults over ``ReplicaDown`` /
``StallDispatch`` / ``JitterDispatch`` against a 4-replica pool serving
a pure transform under closed-loop client load, with the gray-failure
guard armed (:func:`run_serving_schedule`). Invariants:

1. **zero lost requests** — every client request succeeds within its
   bounded typed-error retry budget;
2. **zero duplicate / mis-versioned responses** — every response is
   bitwise equal to the reference transform of exactly its own rows,
   and all responses name one model version (a hedge double-count or an
   abandoned straggler leaking through would break this);
3. **p99 recovery** — after the faults clear and quarantined replicas
   rejoin, closed-loop p99 returns to ≤ 2x the pre-fault baseline
   (plus an absolute floor for timer noise).

Failing schedules shrink through the same :func:`shrink_schedule`
ddmin and commit the same ``FaultPlan`` JSON repro artifact.

**Worker soak** (``--worker``): the trainer soak's restart invariants
exercised across a REAL process boundary. Each schedule draws from the
``cluster.worker`` seam (hard ``os._exit`` mid-stream via
:class:`~flinkml_tpu.faults.WorkerCrash` — crash-once markers keep a
restarted child from dying at the same trigger forever) alongside the
in-loop numerics/crash seams; the scenario runs in a CHILD process
(:func:`run_worker_schedule`) and the parent restarts it on every
nonzero exit exactly like an orchestrator supervising a worker pool.
The invariants are the trainer soak's, now with nothing shared between
incarnations but the checkpoint directory: no silent fresh start
(model version), ledger parity, bit-exact coefficients vs golden.
"""

from __future__ import annotations

import dataclasses
import os
import tempfile
import threading
import time
from typing import Any, Callable, Dict, FrozenSet, List, Optional, Tuple

import numpy as np

from flinkml_tpu import faults as faults_mod
from flinkml_tpu.recovery.policy import RecoveryPolicy
from flinkml_tpu.recovery.sentinel import NumericsError, NumericsSentinel
from flinkml_tpu.utils.logging import get_logger

_log = get_logger("recovery.fuzz")

#: The soak scenario (small on purpose: 25+ schedules must fit a CI
#: wall-clock budget; every jitted program is shared across schedules).
SCENARIO_BATCHES = 10
SCENARIO_ROWS = 32
SCENARIO_DIM = 4
SCENARIO_ALPHA = 0.5
SCENARIO_INTERVAL = 2
_POISON_FAULTS = ("NaNGrad", "InfLoss", "PoisonBatch")


def scenario_dataset(seed: int = 0):
    """The soak's feed: a synthetic :class:`~flinkml_tpu.data.Dataset`
    (so the ``data.read`` seam is live), deterministic in ``seed``."""
    from flinkml_tpu.data import Dataset
    from flinkml_tpu.table import Table

    true = np.arange(1.0, SCENARIO_DIM + 1.0)

    def mk(i, rng):
        x = rng.normal(size=(SCENARIO_ROWS, SCENARIO_DIM))
        return Table({
            "features": x,
            "label": (x @ true > 0).astype(np.float64),
        })

    return Dataset.synthetic(mk, SCENARIO_BATCHES, seed=seed)


def scenario_batches(seed: int = 0) -> List[Any]:
    """The same feed materialized as a list (golden runs filter it)."""
    return list(scenario_dataset(seed))


def _fit(feed, manager, resume: bool, self_heal: bool):
    from flinkml_tpu.models import OnlineLogisticRegression

    kwargs: Dict[str, Any] = {}
    if self_heal:
        kwargs["recovery"] = RecoveryPolicy(backoff_s=0.0)
        kwargs["sentinel"] = NumericsSentinel()
    return OnlineLogisticRegression().set_alpha(SCENARIO_ALPHA).fit_stream(
        feed, checkpoint_manager=manager,
        checkpoint_interval=SCENARIO_INTERVAL, resume=resume, **kwargs,
    )


class GoldenCache:
    """Golden models per exclusion set (the run with the quarantined
    batches excluded), computed lazily — most schedules share the empty
    exclusion."""

    def __init__(self, seed: int = 0):
        self._batches = scenario_batches(seed)
        self._cache: Dict[FrozenSet[int], Any] = {}

    def model(self, excluded: FrozenSet[int]):
        key = frozenset(int(i) for i in excluded)
        if key not in self._cache:
            from flinkml_tpu.models import OnlineLogisticRegression

            kept = [b for i, b in enumerate(self._batches)
                    if i not in key]
            self._cache[key] = (
                OnlineLogisticRegression().set_alpha(SCENARIO_ALPHA)
                .fit_stream(kept)
            )
        return self._cache[key]


def expected_quarantine(plan: "faults_mod.FaultPlan") -> FrozenSet[int]:
    """The batches a schedule's numerics faults poison — what a
    consistent ledger must name exactly."""
    out = set()
    for f in plan.faults:
        name = type(f).__name__
        if name in ("NaNGrad", "InfLoss"):
            out.add(int(f.at_epoch))
        elif name == "PoisonBatch":
            out.add(int(f.at_batch))
    return frozenset(i for i in out if 0 <= i < SCENARIO_BATCHES)


@dataclasses.dataclass
class ScheduleResult:
    index: int
    faults: List[str]
    ok: bool
    failures: List[str]
    restarts: int
    quarantined: List[int]
    elapsed_s: float


def run_schedule(plan: "faults_mod.FaultPlan", golden: GoldenCache,
                 data_seed: int = 0, self_heal: bool = True,
                 max_restarts: int = 10) -> Tuple[Any, List[str], int]:
    """Run the scenario under ``plan``: the trainer is restarted on
    every scripted crash (``FaultInjected`` — the orchestrator's role),
    numerics faults are healed in-loop when ``self_heal``. Returns
    ``(model_or_None, invariant_failures, restarts)``."""
    failures: List[str] = []
    model = None
    restarts = 0
    with tempfile.TemporaryDirectory(prefix="fuzz-ckpt-") as td:
        from flinkml_tpu.iteration import CheckpointManager
        from flinkml_tpu.iteration.checkpoint import (
            CheckpointIntegrityError,
        )

        manager = CheckpointManager(td, max_to_keep=10)
        with faults_mod.armed(plan):
            while True:
                try:
                    model = _fit(scenario_dataset(data_seed), manager,
                                 resume=restarts > 0, self_heal=self_heal)
                    break
                except faults_mod.FaultInjected:
                    restarts += 1
                    if restarts > max_restarts:
                        failures.append(
                            f"did not complete within {max_restarts} "
                            "restarts"
                        )
                        break
                except NumericsError as e:
                    failures.append(f"unhealed numerics failure: {e}")
                    break
        # The on-disk ledger: what the newest valid snapshot recorded
        # (what a NEXT resume would honor). read_extra is carry-shape-
        # independent; the epoch just passed verify(), so a failure
        # here is a real regression in ledger persistence — recorded as
        # an invariant failure, never a vacuously-empty disk ledger.
        recorded = None
        epoch = manager.newest_valid_epoch()
        if epoch is not None:
            try:
                recorded = manager.read_extra(epoch).get("quarantine")
            except CheckpointIntegrityError as e:
                failures.append(
                    f"snapshot {epoch} passed verify() but its extra "
                    f"manifest is unreadable: {e}"
                )
    from flinkml_tpu.recovery.policy import QuarantineLedger

    disk_ledger = QuarantineLedger.from_json_dict(recorded).indices()

    if model is not None:
        expected = expected_quarantine(plan) if self_heal else frozenset()
        summary = getattr(model, "recovery_summary", None) or {}
        quarantined = summary.get("quarantined", [])
        if not np.isfinite(model.coefficient).all():
            failures.append("final model is not finite")
        want_version = SCENARIO_BATCHES - len(expected)
        if model.model_version != want_version:
            failures.append(
                f"model version {model.model_version} != "
                f"{want_version} (batches - quarantined: silent fresh "
                "start or mis-counted poison)"
            )
        if self_heal:
            # The run's quarantines carry across restarts via the
            # snapshot ledger; the final restart's summary plus the
            # resumed skips must name exactly the poisoned batches —
            # read the union of the summary and the on-disk record.
            seen = set(quarantined) | set(disk_ledger)
            if seen != set(expected):
                failures.append(
                    f"quarantine ledger {sorted(seen)} != poisoned "
                    f"batches {sorted(expected)}"
                )
            if not set(disk_ledger) <= set(expected):
                failures.append(
                    f"on-disk ledger {disk_ledger} names batches no "
                    f"fault poisoned ({sorted(expected)})"
                )
        if not failures:
            ref = golden.model(expected)
            if not np.array_equal(model.coefficient, ref.coefficient):
                failures.append(
                    "final model != golden run with the quarantined "
                    "batches excluded"
                )
    elif not failures:
        failures.append("no model produced")
    return model, failures, restarts


def shrink_schedule(plan: "faults_mod.FaultPlan",
                    still_fails: Callable[["faults_mod.FaultPlan"], bool]
                    ) -> "faults_mod.FaultPlan":
    """Greedy delta-debugging over the fault list: drop every fault
    whose removal keeps ``still_fails`` true; repeat until stable. Each
    probe runs a FRESH plan (fired flags reset via spec round-trip), so
    probes never contaminate each other."""
    specs = [faults_mod.fault_to_spec(f) for f in plan.faults]

    def build(subset):
        return faults_mod.FaultPlan(
            *[faults_mod.fault_from_spec(dict(s)) for s in subset]
        )

    changed = True
    while changed and len(specs) > 1:
        changed = False
        for i in range(len(specs)):
            candidate = specs[:i] + specs[i + 1:]
            if still_fails(build(candidate)):
                specs = candidate
                changed = True
                break
    return build(specs)


@dataclasses.dataclass
class SoakReport:
    seed: int
    results: List[ScheduleResult]
    elapsed_s: float
    budget: int
    #: Schedules skipped because the wall-clock budget ran out (0 when
    #: the soak covered the full budget) — never silently truncated.
    skipped: int = 0

    @property
    def ok(self) -> bool:
        return self.skipped == 0 and all(r.ok for r in self.results)

    @property
    def failures(self) -> List[ScheduleResult]:
        return [r for r in self.results if not r.ok]

    def summary(self) -> str:
        n_q = sum(len(r.quarantined) for r in self.results)
        n_r = sum(r.restarts for r in self.results)
        return (
            f"chaos soak seed={self.seed}: {len(self.results)}/"
            f"{self.budget} schedules, {len(self.failures)} failed, "
            f"{n_r} restarts, {n_q} quarantined batches, "
            f"{self.elapsed_s:.1f}s"
            + (f" ({self.skipped} SKIPPED on wall budget)"
               if self.skipped else "")
        )


def run_soak(seed: int = 7, budget: int = 25,
             wall_budget_s: Optional[float] = None,
             fuzz: Optional["faults_mod.FuzzPlan"] = None,
             repro_dir: Optional[str] = None,
             data_seed: int = 0) -> SoakReport:
    """The full soak: ``budget`` sampled schedules, invariants asserted,
    every failing schedule shrunk and (when ``repro_dir`` is given)
    committed as a minimal ``FaultPlan`` JSON repro."""
    fuzz = fuzz or faults_mod.FuzzPlan(
        seed=seed, budget=budget, horizon=SCENARIO_BATCHES
    )
    golden = GoldenCache(data_seed)
    golden.model(frozenset())  # warm the jits outside the timed window
    t0 = time.perf_counter()
    results: List[ScheduleResult] = []
    skipped = 0
    for index, plan in fuzz.schedules():
        if (wall_budget_s is not None
                and time.perf_counter() - t0 > wall_budget_s):
            skipped = fuzz.budget - index
            _log.warning(
                "soak wall budget (%ss) exhausted at schedule %d/%d",
                wall_budget_s, index, fuzz.budget,
            )
            break
        st = time.perf_counter()
        descs = [f.describe() for f in plan.faults]
        _, failures, restarts = run_schedule(
            plan, golden, data_seed=data_seed
        )
        # Re-read the expected set for the record (the ledger equals it
        # on a green schedule).
        expected = sorted(expected_quarantine(plan))
        result = ScheduleResult(
            index=index, faults=descs, ok=not failures,
            failures=failures, restarts=restarts,
            quarantined=expected if not failures else [],
            elapsed_s=round(time.perf_counter() - st, 3),
        )
        results.append(result)
        if failures:
            _log.error("schedule %d FAILED %s: %s", index, descs, failures)
            if repro_dir is not None:
                minimal = shrink_schedule(
                    plan,
                    lambda p: bool(
                        run_schedule(p, golden, data_seed=data_seed)[1]
                    ),
                )
                os.makedirs(repro_dir, exist_ok=True)
                path = os.path.join(
                    repro_dir, f"fuzz_repro_seed{seed}_sched{index}.json"
                )
                with open(path, "w") as f:
                    f.write(faults_mod.plan_to_json(minimal, extra={
                        "seed": seed, "schedule": index,
                        "failures": failures,
                        "scenario": {
                            "batches": SCENARIO_BATCHES,
                            "rows": SCENARIO_ROWS,
                            "dim": SCENARIO_DIM,
                            "alpha": SCENARIO_ALPHA,
                            "checkpoint_interval": SCENARIO_INTERVAL,
                            "data_seed": data_seed,
                        },
                    }))
                _log.error("minimal repro written: %s (%d -> %d faults)",
                           path, len(plan.faults), len(minimal.faults))
        else:
            _log.info("schedule %d ok %s (restarts=%d)", index, descs,
                      restarts)
    report = SoakReport(
        seed=seed, results=results,
        elapsed_s=round(time.perf_counter() - t0, 2),
        budget=fuzz.budget, skipped=skipped,
    )
    _log.warning("%s", report.summary())
    return report


# ---------------------------------------------------------------------------
# Worker soak: the same invariants across a real process boundary
# ---------------------------------------------------------------------------

#: Exit code the child uses for an in-loop scripted crash
#: (``FaultInjected``) — distinct from WorkerCrash's sampled hard-exit
#: codes (20–29) and from real child failures.
WORKER_RESTART_EXIT = 3
WORKER_CHILD_TIMEOUT_S = 180.0


def _worker_child_main(workdir: str, resume: bool) -> int:
    """One incarnation of the soak trainer, run in its own process.

    Reads ``<workdir>/plan.json``, arms it, and runs the scenario with
    checkpoints under ``<workdir>/ckpt`` — firing the ``cluster.worker``
    seam once per batch so a sampled :class:`WorkerCrash` is a REAL
    ``os._exit`` mid-stream. An in-loop scripted crash
    (``FaultInjected``) exits :data:`WORKER_RESTART_EXIT`; success
    writes ``<workdir>/result.json`` and exits 0. The orchestrator
    (parent) restarts on any nonzero exit."""
    import json

    with open(os.path.join(workdir, "plan.json")) as f:
        raw = f.read()
    plan = faults_mod.plan_from_json(raw)
    extras = json.loads(raw)
    data_seed = int(extras.get("data_seed", 0))
    if extras.get("x64"):
        # Mirror the parent's precision: the env-var form of this flag
        # is not honored by this jax build, so the parent ships its
        # config-level setting through the plan file.
        import jax

        jax.config.update("jax_enable_x64", True)

    # Fired-flag persistence across INCARNATIONS: the in-process soak's
    # armed plan object survives its restart loop, so a scripted crash
    # fires once. Here every incarnation re-arms a fresh plan from
    # JSON, so fired flags are carried in the workdir instead —
    # WorkerCrash has its own marker file; the in-loop faults get this.
    fired_path = os.path.join(workdir, "fired.json")
    fired_idx: set = set()
    if os.path.exists(fired_path):
        with open(fired_path) as f:
            fired_idx = set(json.load(f))
    for i in fired_idx:
        plan.faults[i].fired = True

    from flinkml_tpu.iteration import CheckpointManager

    manager = CheckpointManager(os.path.join(workdir, "ckpt"),
                                max_to_keep=10)

    # The per-batch worker heartbeat, as a map op so the feed STAYS a
    # replayable Dataset (quarantine retries re-open it from the
    # cursor): where a pool worker would be serving a request, the soak
    # trainer is reading a batch. The counter is monotone across
    # replays; WorkerCrash's marker keeps each crash once-per-run.
    reads = [0]

    def heartbeat(batch):
        reads[0] += 1
        if faults_mod.ACTIVE is not None:
            faults_mod.fire("cluster.worker", epoch=reads[0] - 1)
        return batch

    feed = scenario_dataset(data_seed).map(heartbeat)
    with faults_mod.armed(plan):
        try:
            model = _fit(feed, manager, resume=resume, self_heal=True)
        except faults_mod.FaultInjected:
            fired_now = fired_idx | {
                i for i, f in enumerate(plan.faults)
                if getattr(f, "fired", False)
            }
            with open(fired_path, "w") as f:
                json.dump(sorted(fired_now), f)
            return WORKER_RESTART_EXIT
    summary = getattr(model, "recovery_summary", None) or {}
    with open(os.path.join(workdir, "result.json"), "w") as f:
        json.dump({
            "model_version": int(model.model_version),
            "coefficient": np.asarray(model.coefficient).tolist(),
            "quarantined": sorted(
                int(i) for i in summary.get("quarantined", [])
            ),
            "finite": bool(np.isfinite(model.coefficient).all()),
        }, f)
    return 0


def run_worker_schedule(plan: "faults_mod.FaultPlan", golden: GoldenCache,
                        data_seed: int = 0, max_restarts: int = 10
                        ) -> Tuple[Optional[Dict[str, Any]], List[str], int]:
    """Run one schedule with the trainer in a CHILD process and this
    process as the orchestrator: every nonzero child exit — an in-loop
    scripted crash OR a WorkerCrash hard ``os._exit`` — is answered
    with a restart (``resume=True``), sharing nothing with the previous
    incarnation but the checkpoint directory. Returns
    ``(result_dict_or_None, invariant_failures, restarts)``."""
    import json
    import subprocess
    import sys

    failures: List[str] = []
    result: Optional[Dict[str, Any]] = None
    restarts = 0
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    with tempfile.TemporaryDirectory(prefix="fuzz-worker-") as td:
        import jax

        with open(os.path.join(td, "plan.json"), "w") as f:
            f.write(faults_mod.plan_to_json(plan, extra={
                "data_seed": int(data_seed),
                "x64": bool(jax.config.jax_enable_x64),
            }))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # the soak child is a CPU process
        env["PYTHONPATH"] = os.pathsep.join(
            x for x in (repo_root, env.get("PYTHONPATH")) if x
        )
        while True:
            argv = [sys.executable, "-m", "flinkml_tpu.recovery.fuzz",
                    "--worker-child", td]
            if restarts > 0:
                argv.append("--resume")
            proc = subprocess.run(
                argv, env=env, capture_output=True, text=True,
                timeout=WORKER_CHILD_TIMEOUT_S,
            )
            if proc.returncode == 0:
                break
            restarts += 1
            if restarts > max_restarts:
                failures.append(
                    f"did not complete within {max_restarts} restarts "
                    f"(last rc={proc.returncode}); stderr tail: "
                    f"{proc.stderr[-500:]}"
                )
                break
        # The on-disk ledger, read the same way run_schedule reads it —
        # it is the only state the NEXT incarnation would honor.
        from flinkml_tpu.iteration import CheckpointManager
        from flinkml_tpu.iteration.checkpoint import (
            CheckpointIntegrityError,
        )

        recorded = None
        manager = CheckpointManager(os.path.join(td, "ckpt"),
                                    max_to_keep=10)
        epoch = manager.newest_valid_epoch()
        if epoch is not None:
            try:
                recorded = manager.read_extra(epoch).get("quarantine")
            except CheckpointIntegrityError as e:
                failures.append(
                    f"snapshot {epoch} passed verify() but its extra "
                    f"manifest is unreadable: {e}"
                )
        result_path = os.path.join(td, "result.json")
        if os.path.exists(result_path):
            with open(result_path) as f:
                result = json.load(f)
    from flinkml_tpu.recovery.policy import QuarantineLedger

    disk_ledger = QuarantineLedger.from_json_dict(recorded).indices()

    if result is not None:
        expected = expected_quarantine(plan)
        coeff = np.asarray(result["coefficient"])
        if not result["finite"] or not np.isfinite(coeff).all():
            failures.append("final model is not finite")
        want_version = SCENARIO_BATCHES - len(expected)
        if result["model_version"] != want_version:
            failures.append(
                f"model version {result['model_version']} != "
                f"{want_version} (batches - quarantined: silent fresh "
                "start across the process boundary)"
            )
        seen = set(result["quarantined"]) | set(disk_ledger)
        if seen != set(expected):
            failures.append(
                f"quarantine ledger {sorted(seen)} != poisoned "
                f"batches {sorted(expected)}"
            )
        if not set(disk_ledger) <= set(expected):
            failures.append(
                f"on-disk ledger {disk_ledger} names batches no "
                f"fault poisoned ({sorted(expected)})"
            )
        if not failures:
            ref = golden.model(expected)
            if not np.array_equal(coeff, np.asarray(ref.coefficient)):
                failures.append(
                    "final model != golden run with the quarantined "
                    "batches excluded (resume across the process "
                    "boundary diverged)"
                )
    elif not failures:
        failures.append("no result produced")
    return result, failures, restarts


def run_worker_soak(seed: int = 7, budget: int = 4,
                    wall_budget_s: Optional[float] = None,
                    fuzz: Optional["faults_mod.FuzzPlan"] = None,
                    repro_dir: Optional[str] = None,
                    data_seed: int = 0) -> SoakReport:
    """The process-boundary soak: ``budget`` schedules over the
    ``cluster.worker`` seam mixed with the in-loop crash/numerics
    seams, each run via :func:`run_worker_schedule`. Budget defaults
    small: every restart pays a full child-interpreter spin-up."""
    with tempfile.TemporaryDirectory(prefix="fuzz-markers-") as markers:
        fuzz = fuzz or faults_mod.FuzzPlan(
            seed=seed,
            seams=("cluster.worker", "iteration.epoch", "train.step"),
            budget=budget, horizon=SCENARIO_BATCHES, max_faults=2,
            marker_dir=markers,
        )
        golden = GoldenCache(data_seed)
        golden.model(frozenset())
        t0 = time.perf_counter()
        results: List[ScheduleResult] = []
        skipped = 0
        for index, plan in fuzz.schedules():
            if (wall_budget_s is not None
                    and time.perf_counter() - t0 > wall_budget_s):
                skipped = fuzz.budget - index
                _log.warning(
                    "worker soak wall budget (%ss) exhausted at "
                    "schedule %d/%d", wall_budget_s, index, fuzz.budget,
                )
                break
            st = time.perf_counter()
            descs = [f.describe() for f in plan.faults]
            _, failures, restarts = run_worker_schedule(
                plan, golden, data_seed=data_seed
            )
            expected = sorted(expected_quarantine(plan))
            results.append(ScheduleResult(
                index=index, faults=descs, ok=not failures,
                failures=failures, restarts=restarts,
                quarantined=expected if not failures else [],
                elapsed_s=round(time.perf_counter() - st, 3),
            ))
            if failures:
                _log.error("worker schedule %d FAILED %s: %s",
                           index, descs, failures)
                if repro_dir is not None:
                    minimal = shrink_schedule(
                        plan,
                        lambda p: bool(run_worker_schedule(
                            p, golden, data_seed=data_seed)[1]),
                    )
                    os.makedirs(repro_dir, exist_ok=True)
                    path = os.path.join(
                        repro_dir,
                        f"fuzz_worker_repro_seed{seed}_sched{index}.json",
                    )
                    with open(path, "w") as f:
                        f.write(faults_mod.plan_to_json(minimal, extra={
                            "seed": seed, "schedule": index,
                            "failures": failures,
                            "scenario": {
                                "kind": "worker",
                                "batches": SCENARIO_BATCHES,
                                "rows": SCENARIO_ROWS,
                                "dim": SCENARIO_DIM,
                                "alpha": SCENARIO_ALPHA,
                                "checkpoint_interval": SCENARIO_INTERVAL,
                                "data_seed": data_seed,
                            },
                        }))
                    _log.error(
                        "minimal worker repro written: %s (%d -> %d "
                        "faults)", path, len(plan.faults),
                        len(minimal.faults),
                    )
            else:
                _log.info("worker schedule %d ok %s (restarts=%d)",
                          index, descs, restarts)
        report = SoakReport(
            seed=seed, results=results,
            elapsed_s=round(time.perf_counter() - t0, 2),
            budget=fuzz.budget, skipped=skipped,
        )
    _log.warning("worker %s", report.summary())
    return report


# ---------------------------------------------------------------------------
# Serving soak: gray-failure schedules against a live replica pool
# ---------------------------------------------------------------------------

#: The serving scenario (sized so each schedule — pool spin-up, client
#: load, recovery probe — fits a few seconds of CI wall clock).
SERVING_REPLICAS = 4
SERVING_CLIENTS = 4
SERVING_REQUESTS = 25
SERVING_ROWS = 8
SERVING_DIM = 4
SERVING_BASELINE_REQUESTS = 60


def serving_grayfail_policy():
    """The soak's :class:`~flinkml_tpu.serving.GrayFailPolicy`: the
    production floors scaled down so the defense is LIVE at CPU-mesh
    latencies (sampled stalls are 50–300 ms; the default 250 ms
    abandonment floor would sleep through half of them)."""
    from flinkml_tpu.serving import GrayFailPolicy

    return GrayFailPolicy(
        attempt_floor_ms=40.0, min_attempt_samples=8,
        hedge_floor_ms=30.0,
        min_slow_samples=8, slow_trip=2, slow_clear=2,
        slow_abs_floor_ms=10.0,
        canary_interval_s=0.05, canary_timeout_ms=500.0,
        quarantine_retire_s=10.0,
        brownout=False,  # single-model pool: no SLO classes to shed
    )


def serving_scenario(seed: int = 0):
    """The serving feed: a fitted pure (elementwise, hedge-idempotent)
    transform plus every client request's features and their reference
    outputs. Elementwise on purpose — each output row depends only on
    its own input row, so the reference computed in one shot is bitwise
    comparable to pool responses regardless of how continuous batching
    coalesced or padded the requests."""
    from flinkml_tpu.models import StandardScaler
    from flinkml_tpu.table import Table

    rng = np.random.default_rng([seed, 17])
    n = SERVING_CLIENTS * SERVING_REQUESTS * SERVING_ROWS
    x = rng.normal(size=(n, SERVING_DIM))
    model = (
        StandardScaler()
        .set(StandardScaler.INPUT_COL, "features")
        .set(StandardScaler.OUTPUT_COL, "scaled")
        .fit(Table({"features": x[:256]}))
    )
    (ref,) = model.transform(Table({"features": x}))
    return model, x, np.asarray(ref.column("scaled"))


def _p99(samples_ms: List[float]) -> float:
    ordered = sorted(samples_ms)
    import math

    return ordered[min(len(ordered) - 1,
                       math.ceil(0.99 * len(ordered)) - 1)]


def run_serving_schedule(plan: "faults_mod.FaultPlan",
                         scenario: Optional[Tuple[Any, Any, Any]] = None,
                         data_seed: int = 0, max_retries: int = 8
                         ) -> Tuple[List[str], Dict[str, Any]]:
    """Run the closed-loop serving scenario under ``plan`` with the
    gray-failure guard armed; returns ``(invariant_failures, stats)``.

    Phases: (1) un-faulted baseline load seeds every replica's attempt
    ring and measures baseline p99; (2) the fault plan arms and
    ``SERVING_CLIENTS`` closed-loop clients each issue
    ``SERVING_REQUESTS`` requests, retrying only on TYPED backpressure
    (overload / unavailable / timeout) with bounded budget; (3) faults
    disarm, quarantined replicas are given time to canary-rejoin, and a
    recovery probe re-measures p99. Invariants per module docstring.
    """
    from flinkml_tpu.serving import (
        PoolUnavailableError,
        ReplicaPool,
        ServingConfig,
        ServingOverloadError,
        ServingTimeoutError,
        ReplicaState,
    )
    from flinkml_tpu.table import Table

    model, x, expected = scenario or serving_scenario(data_seed)
    failures: List[str] = []
    pool = ReplicaPool(
        model, Table({"features": x[:4]}),
        config=ServingConfig(max_batch_rows=64, max_queue_rows=512,
                             max_wait_ms=1.0, default_timeout_ms=15_000.0),
        n_replicas=SERVING_REPLICAS, output_cols=("scaled",),
        name="soak", grayfail=serving_grayfail_policy(),
    )
    guard = pool.grayfail_guard(interval_s=0.05)
    retryable = (ServingOverloadError, PoolUnavailableError,
                 ServingTimeoutError)
    lock = threading.Lock()
    lost: List[str] = []
    mismatched: List[str] = []
    versions: set = set()
    retries = [0]
    stats: Dict[str, Any] = {}

    def one_request(sl, tag: str) -> Optional[float]:
        """One closed-loop request; parity-checked. Returns latency ms
        (None when lost after the retry budget)."""
        feats = {"features": x[sl]}
        t0 = time.perf_counter()
        for attempt in range(max_retries + 1):
            try:
                resp = pool.predict(feats, timeout_ms=5_000.0)
            except retryable:
                with lock:
                    retries[0] += 1
                time.sleep(0.01 * (attempt + 1))
                continue
            latency = (time.perf_counter() - t0) * 1e3
            got = np.asarray(resp.columns["scaled"])
            with lock:
                versions.add(resp.version)
                if not np.array_equal(got, expected[sl]):
                    mismatched.append(
                        f"{tag}: response is not the reference transform "
                        "of its own rows (duplicate/mixed/mis-versioned)"
                    )
            return latency
        with lock:
            lost.append(f"{tag}: lost after {max_retries} typed-error "
                        "retries")
        return None

    def closed_loop(client: int):
        for i in range(SERVING_REQUESTS):
            start = (client * SERVING_REQUESTS + i) * SERVING_ROWS
            lat = one_request(slice(start, start + SERVING_ROWS),
                              f"client {client} request {i}")
            if lat is not None:
                with lock:
                    faulted_ms.append(lat)
            # Think time: stretches the load window across several guard
            # evaluations so quarantine/rejoin actually happen DURING
            # traffic (a CPU-mesh request is ~1 ms; without this the
            # whole faulted phase fits inside one sampled stall).
            time.sleep(0.005)

    try:
        pool.start()
        # Phase 1: baseline (also seeds the sibling attempt rings the
        # abandonment budget needs).
        baseline_ms = []
        for i in range(SERVING_BASELINE_REQUESTS):
            start = (i % (SERVING_CLIENTS * SERVING_REQUESTS)) * SERVING_ROWS
            lat = one_request(slice(start, start + SERVING_ROWS),
                              f"baseline {i}")
            if lat is not None:
                baseline_ms.append(lat)
        if lost:
            return lost + ["baseline load lost requests; aborting"], stats
        p99_base = _p99(baseline_ms)
        # Phase 2: faulted closed-loop load.
        faulted_ms: List[float] = []
        guard.start()
        with faults_mod.armed(plan):
            threads = [
                threading.Thread(target=closed_loop, args=(c,),
                                 name=f"soak-client-{c}", daemon=True)
                for c in range(SERVING_CLIENTS)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        # Phase 3: faults disarmed — wait for SLOW replicas to
        # canary-rejoin, then probe recovered p99.
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if not any(r.health.state is ReplicaState.SLOW
                       for r in pool.replicas):
                break
            time.sleep(0.05)
        still_slow = [r.name for r in pool.replicas
                      if r.health.state is ReplicaState.SLOW]
        if still_slow:
            failures.append(
                f"replicas {still_slow} never rejoined after the faults "
                "cleared (canary/rejoin path broken)"
            )
        recovered_ms = []
        for i in range(SERVING_BASELINE_REQUESTS):
            start = (i % (SERVING_CLIENTS * SERVING_REQUESTS)) * SERVING_ROWS
            lat = one_request(slice(start, start + SERVING_ROWS),
                              f"recovery {i}")
            if lat is not None:
                recovered_ms.append(lat)
        p99_rec = _p99(recovered_ms) if recovered_ms else float("inf")
        failures.extend(lost)
        failures.extend(mismatched)
        if len(versions) > 1:
            failures.append(
                f"responses named {len(versions)} distinct model "
                f"versions ({sorted(versions)}); expected exactly one"
            )
        # ≤ 2x baseline, with an absolute floor so timer noise on a
        # sub-ms baseline can't flake the invariant.
        bound = max(2.0 * p99_base, p99_base + 50.0)
        if p99_rec > bound:
            failures.append(
                f"recovered p99 {p99_rec:.1f}ms > bound {bound:.1f}ms "
                f"(baseline {p99_base:.1f}ms): pool did not recover"
            )
        per_replica = {r.name: r.health.state.value for r in pool.replicas}
        stats.update({
            "p99_baseline_ms": round(p99_base, 2),
            "p99_faulted_ms": round(_p99(faulted_ms), 2)
            if faulted_ms else None,
            "p99_recovered_ms": round(p99_rec, 2),
            "retries": retries[0],
            "replica_states": per_replica,
        })
    finally:
        guard.stop()
        pool.stop(drain=False, timeout=5.0)
    return failures, stats


@dataclasses.dataclass
class ServingScheduleResult:
    index: int
    faults: List[str]
    ok: bool
    failures: List[str]
    stats: Dict[str, Any]
    elapsed_s: float


@dataclasses.dataclass
class ServingSoakReport:
    seed: int
    results: List[ServingScheduleResult]
    elapsed_s: float
    budget: int
    skipped: int = 0

    @property
    def ok(self) -> bool:
        return self.skipped == 0 and all(r.ok for r in self.results)

    @property
    def failures(self) -> List[ServingScheduleResult]:
        return [r for r in self.results if not r.ok]

    def summary(self) -> str:
        n_retries = sum(r.stats.get("retries", 0) for r in self.results)
        return (
            f"serving soak seed={self.seed}: {len(self.results)}/"
            f"{self.budget} schedules, {len(self.failures)} failed, "
            f"{n_retries} typed-error retries, {self.elapsed_s:.1f}s"
            + (f" ({self.skipped} SKIPPED on wall budget)"
               if self.skipped else "")
        )


def run_serving_soak(seed: int = 7, budget: int = 6,
                     wall_budget_s: Optional[float] = None,
                     fuzz: Optional["faults_mod.FuzzPlan"] = None,
                     repro_dir: Optional[str] = None,
                     data_seed: int = 0) -> ServingSoakReport:
    """The serving-pool soak: ``budget`` schedules over the
    ``serving.replica`` seam, each run with :func:`run_serving_schedule`;
    failing schedules shrink through :func:`shrink_schedule` and commit
    the same JSON repro artifact as the trainer soak."""
    fuzz = fuzz or faults_mod.FuzzPlan(
        seed=seed, seams=("serving.replica",), budget=budget,
        horizon=8, max_faults=3, replicas=SERVING_REPLICAS,
    )
    scenario = serving_scenario(data_seed)
    t0 = time.perf_counter()
    results: List[ServingScheduleResult] = []
    skipped = 0
    for index, plan in fuzz.schedules():
        if (wall_budget_s is not None
                and time.perf_counter() - t0 > wall_budget_s):
            skipped = fuzz.budget - index
            _log.warning(
                "serving soak wall budget (%ss) exhausted at schedule "
                "%d/%d", wall_budget_s, index, fuzz.budget,
            )
            break
        st = time.perf_counter()
        descs = [f.describe() for f in plan.faults]
        failures, stats = run_serving_schedule(
            plan, scenario=scenario, data_seed=data_seed
        )
        results.append(ServingScheduleResult(
            index=index, faults=descs, ok=not failures,
            failures=failures, stats=stats,
            elapsed_s=round(time.perf_counter() - st, 3),
        ))
        if failures:
            _log.error("serving schedule %d FAILED %s: %s",
                       index, descs, failures)
            if repro_dir is not None:
                minimal = shrink_schedule(
                    plan,
                    lambda p: bool(run_serving_schedule(
                        p, scenario=scenario, data_seed=data_seed)[0]),
                )
                os.makedirs(repro_dir, exist_ok=True)
                path = os.path.join(
                    repro_dir,
                    f"fuzz_serving_repro_seed{seed}_sched{index}.json",
                )
                with open(path, "w") as f:
                    f.write(faults_mod.plan_to_json(minimal, extra={
                        "seed": seed, "schedule": index,
                        "failures": failures,
                        "scenario": {
                            "kind": "serving",
                            "replicas": SERVING_REPLICAS,
                            "clients": SERVING_CLIENTS,
                            "requests_per_client": SERVING_REQUESTS,
                            "rows_per_request": SERVING_ROWS,
                            "dim": SERVING_DIM,
                            "data_seed": data_seed,
                        },
                    }))
                _log.error("minimal serving repro written: %s (%d -> %d "
                           "faults)", path, len(plan.faults),
                           len(minimal.faults))
        else:
            _log.info("serving schedule %d ok %s (%s)", index, descs,
                      stats)
    report = ServingSoakReport(
        seed=seed, results=results,
        elapsed_s=round(time.perf_counter() - t0, 2),
        budget=fuzz.budget, skipped=skipped,
    )
    _log.warning("%s", report.summary())
    return report


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        description="flinkml_tpu chaos soak (device-free; run under "
                    "JAX_PLATFORMS=cpu)"
    )
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--budget", type=int, default=None)
    parser.add_argument("--wall-budget-s", type=float, default=None)
    parser.add_argument("--repro-dir", default=None,
                        help="write minimal FaultPlan repros for failing "
                             "schedules here")
    parser.add_argument("--serving", action="store_true",
                        help="run the serving-pool gray-failure soak "
                             "instead of the trainer soak")
    parser.add_argument("--worker", action="store_true",
                        help="run the process-boundary worker-crash soak "
                             "(each schedule's trainer is a supervised "
                             "child process)")
    parser.add_argument("--worker-child", metavar="DIR", default=None,
                        help=argparse.SUPPRESS)  # internal: one incarnation
    parser.add_argument("--resume", action="store_true",
                        help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.worker_child:
        return _worker_child_main(args.worker_child, resume=args.resume)
    if args.worker:
        report = run_worker_soak(
            seed=args.seed,
            budget=args.budget if args.budget is not None else 4,
            wall_budget_s=args.wall_budget_s,
            repro_dir=args.repro_dir,
        )
    elif args.serving:
        report = run_serving_soak(
            seed=args.seed,
            budget=args.budget if args.budget is not None else 6,
            wall_budget_s=args.wall_budget_s,
            repro_dir=args.repro_dir,
        )
    else:
        report = run_soak(
            seed=args.seed,
            budget=args.budget if args.budget is not None else 25,
            wall_budget_s=args.wall_budget_s,
            repro_dir=args.repro_dir,
        )
    print(report.summary())
    for r in report.failures:
        print(f"  FAILED schedule {r.index}: {r.faults} -> {r.failures}")
    return 0 if report.ok else 1


if __name__ == "__main__":  # pragma: no cover — CLI shim
    raise SystemExit(main())
