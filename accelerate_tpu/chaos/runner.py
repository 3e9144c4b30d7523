"""ChaosRunner: drive real train/serve workloads under a fault plan and emit a
machine-readable invariant report.

The runner owns the *invariants* the stack promises under faults, checked
against evidence the workload journals as it runs:

  - **resume_exactness** — every restart resumes from the last *committed*
    checkpoint: the resolved manifest's step matches the newest
    independently-verified checkpoint, and the restored parameter digest
    matches what the journal recorded when that step was committed.
  - **no_torn_resolved** — `resolve("latest")` never hands a resume a
    checkpoint whose digests fail. Verification here is INDEPENDENT of
    `checkpointing.verify_checkpoint_dir` (the runner re-hashes files
    itself), so a regression — or the `harness.disable_verification`
    seeded-regression fixture — turns the report red instead of being
    vacuously green.
  - **restart_budget** — restarts and injected downtime stay inside budget,
    and the run actually completes.
  - **terminal_finish_reasons** — under serving faults, every accepted request
    drains to a terminal `finish_reason`; the engine recovers after a
    dispatch failure; the bounded queue never exceeds its cap.
  - **ledger_reconciles** — `chaos_injected_total{kind=...}` counters match
    the injection journal, and injected downtime shows up in the goodput
    ledger (slow fsyncs inside `save_state` land in the "checkpoint" cause,
    resumes in "restart").

Workloads are deliberately tiny (the regression model / a tiny llama) so full
sweeps — SIGKILL at every boundary, torn bytes at every offset — run on CPU in
tier-1 time. `run_supervised_train` additionally drives the real
`fault_tolerance.Supervisor` over a subprocess workload with the plan
propagated via ``ACCELERATE_TPU_FAULT_PLAN`` (`chaos.workload`).
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ..logging import get_logger
from ..telemetry import MetricsRegistry
from ..telemetry.flight_recorder import FlightRecorder, collect_trace_dir
from ..telemetry.tracing import Tracer
from .injectors import (
    ChaosSession,
    FilesystemInjector,
    HarnessInjector,
    InjectedKill,
    RouterInjector,
    ServingInjector,
    StepBoundaryInjector,
)
from .plan import FAULT_PLAN_ENV, FaultPlan

logger = get_logger(__name__)


class _GracefulPreemption(Exception):
    """In-process stand-in for the SIGTERM -> checkpoint -> exit-143 handoff."""


def _reason_counts(finish_reasons: Dict[int, Optional[str]]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for reason in finish_reasons.values():
        key = reason if reason is not None else "none"
        out[key] = out.get(key, 0) + 1
    return out


# ------------------------------------------------------------------ independent evidence
def independent_verify(directory: str) -> bool:
    """Re-hash every file a checkpoint's MANIFEST.json names, with our own
    hashlib walk — NOT `checkpointing.verify_checkpoint_dir`, which a chaos
    plan (or a real regression) may have neutered. The auditor must never
    share machinery with the system it audits."""
    manifest_path = os.path.join(str(directory), "MANIFEST.json")
    try:
        with open(manifest_path) as f:
            manifest = json.load(f)
    except (OSError, ValueError):  # ValueError covers JSON errors AND flipped-byte utf-8 tears
        return False
    for rel, digest in manifest.get("files", {}).items():
        h = hashlib.sha256()
        try:
            with open(os.path.join(str(directory), rel), "rb") as f:
                for chunk in iter(lambda: f.read(1 << 20), b""):
                    h.update(chunk)
        except OSError:
            return False
        if h.hexdigest() != digest:
            return False
    return True


def manifest_step(directory: str) -> Optional[int]:
    try:
        with open(os.path.join(str(directory), "MANIFEST.json")) as f:
            return json.load(f).get("step")
    except (OSError, ValueError):  # ValueError covers JSON errors AND flipped-byte utf-8 tears
        return None


def independent_latest_step(checkpoint_base: str) -> Optional[int]:
    """Newest step among checkpoints that INDEPENDENTLY verify — what a correct
    `resolve("latest")` must land on."""
    best = None
    if not os.path.isdir(checkpoint_base):
        return None
    for name in os.listdir(checkpoint_base):
        path = os.path.join(checkpoint_base, name)
        suffix = name[len("checkpoint_"):] if name.startswith("checkpoint_") else ""
        if not suffix.isdigit() or not os.path.isdir(path):
            continue
        if independent_verify(path):
            step = int(suffix)
            best = step if best is None else max(best, step)
    return best


def params_digest(model) -> str:
    """Content hash of a prepared model's parameters (path-keyed, host-side):
    the resume-exactness fingerprint."""
    from ..checkpointing import _flatten_with_paths

    flat, _ = _flatten_with_paths(model.params)
    h = hashlib.sha256()
    for path, leaf in flat:
        h.update(path.encode())
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


def build_train_workload(
    base_dir: str, keep_last_n: int, seed: int, async_save: bool = False,
    mesh_2d: bool = False,
):
    """The canonical tiny train workload — shared by the in-process runner and
    the subprocess `chaos.workload`, so both sides of the supervised story
    exercise (and journal) the same thing. Returns (accelerator, model, opt,
    prepared_dataloader). `async_save=True` arms snapshot-then-commit saves
    (the async-commit-boundary sweeps' workload). `mesh_2d=True` swaps in the
    small MLP on a ("data", "model") mesh with ``sharding_rules="auto"`` and
    Adam — the planner's 2D plan with ZeRO data-sharded moments, so chaos
    faults land on a sharded optimizer state and resumes can assert the
    layout survived (`zero_state_sharded`)."""
    import optax

    from .. import Accelerator, SimpleDataLoader
    from ..data_loader import BatchSampler
    from ..test_utils.training import RegressionDataset, RegressionMLPModel, RegressionModel
    from ..utils import ParallelismConfig, ProjectConfiguration

    accelerator = Accelerator(
        project_config=ProjectConfiguration(
            project_dir=str(base_dir),
            automatic_checkpoint_naming=True,
            total_limit=keep_last_n,
        ),
        async_save=async_save,
        parallelism_config=ParallelismConfig(data=-1, model=2) if mesh_2d else None,
    )
    n = 16
    data = [RegressionDataset(length=n, seed=seed)[i] for i in range(n)]
    dl = SimpleDataLoader(data, BatchSampler(range(n), 8))
    if mesh_2d:
        bundle = RegressionMLPModel(seed=seed)
        bundle.sharding_rules = "auto"
        tx = optax.adam(0.05)
    else:
        bundle, tx = RegressionModel(), optax.sgd(0.05)
    model, opt, pdl = accelerator.prepare(bundle, tx, dl)
    return accelerator, model, opt, pdl


def opt_state_data_sharded(opt) -> bool:
    """True when some LIVE optimizer-state leaf is sharded along the "data"
    axis — the ZeRO weight-update-sharding layout the 2D planner emits. Read
    off the placed arrays, not the plan: this is the evidence a chaos resume
    journals to prove the layout survived the restore."""
    import jax

    for leaf in jax.tree_util.tree_leaves(getattr(opt, "opt_state", opt)):
        spec = getattr(getattr(leaf, "sharding", None), "spec", None)
        if spec is None:
            continue
        for dim in spec:
            axes = dim if isinstance(dim, tuple) else ((dim,) if dim else ())
            if "data" in axes:
                return True
    return False


def stage_layout_evidence(model) -> Dict[str, Any]:
    """The layout record an MPMD pipeline workload journals before any fault
    lands AND after every resume: the (usually NON-uniform) stage->layer
    assignment and per-stage submesh sizes, read off the live model. A
    restart that silently re-planned to a different split — or fell back to
    a single mesh — would train correctly while erasing exactly the layout
    the chaos run exists to stress."""
    counts = [
        len(model.plan.stage_plan.stage_layers(k)) for k in range(model.num_stages)
    ]
    return {
        "num_stages": model.num_stages,
        "stage_layers": counts,
        "nonuniform": len(set(counts)) > 1,
        "submesh_devices": [int(m.devices.size) for m in model.submeshes],
    }


def resume_evidence(
    resolved: str, model, checkpoint_base: str, opt=None
) -> Dict[str, Any]:
    """The journal record both train workloads write after a resume — one
    schema, one producer, so the invariant checks can never diverge between
    the in-process and subprocess paths. Pass ``opt`` on 2D-mesh workloads to
    record whether the restored optimizer state is still ZeRO-sharded along
    "data" (`zero_state_sharded`) — a resume that silently replicates the
    moments would train correctly while spending data_n x the HBM."""
    evidence = {
        "path": resolved,
        "step": manifest_step(resolved),
        "digest": params_digest(model),
        "independently_verified": independent_verify(resolved),
        "expected_step": independent_latest_step(checkpoint_base),
    }
    if opt is not None:
        evidence["zero_state_sharded"] = opt_state_data_sharded(opt)
    return evidence


# ------------------------------------------------------------------ report
@dataclass
class InvariantCheck:
    name: str
    passed: bool
    details: Dict[str, Any] = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {"name": self.name, "passed": self.passed, "details": self.details}


@dataclass
class InvariantReport:
    """The machine-readable outcome of one chaos run: plan, per-invariant
    verdicts, the injection journal, and a registry snapshot (chaos counters +
    whatever the workload instrumented)."""

    plan: dict
    workload: str
    checks: List[InvariantCheck] = field(default_factory=list)
    injections: List[dict] = field(default_factory=list)
    metrics: List[dict] = field(default_factory=list)
    #: Tagged runner diagnostics that are not invariant verdicts — e.g.
    #: ``{"tag": "crash_loop", ...}`` when a sweep was cut short because the
    #: workload made no forward progress across restarts (the async at_step
    #: SIGKILL livelock): the report says WHY it stopped instead of burning
    #: the whole restart budget on a deterministic loop.
    diagnostics: List[dict] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return all(c.passed for c in self.checks)

    @property
    def violated(self) -> List[InvariantCheck]:
        return [c for c in self.checks if not c.passed]

    def to_dict(self) -> dict:
        return {
            "format": 1,
            "plan": self.plan,
            "workload": self.workload,
            "ok": self.ok,
            "checks": [c.to_dict() for c in self.checks],
            "injections": self.injections,
            "metrics": self.metrics,
            "diagnostics": self.diagnostics,
        }

    def to_json(self, indent: Optional[int] = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    def save(self, path: str) -> str:
        with open(str(path), "w") as f:
            f.write(self.to_json())
        return str(path)

    @classmethod
    def from_dict(cls, data: dict) -> "InvariantReport":
        return cls(
            plan=data.get("plan", {}),
            workload=data.get("workload", "?"),
            checks=[
                InvariantCheck(c["name"], bool(c["passed"]), c.get("details", {}))
                for c in data.get("checks", [])
            ],
            injections=data.get("injections", []),
            metrics=data.get("metrics", []),
            diagnostics=data.get("diagnostics", []),
        )

    @classmethod
    def load(cls, path: str) -> "InvariantReport":
        with open(str(path)) as f:
            return cls.from_dict(json.load(f))

    def render_text(self) -> str:
        lines = [
            f"chaos run: plan={self.plan.get('name', '?')} workload={self.workload} "
            f"injections={len(self.injections)} -> {'OK' if self.ok else 'INVARIANTS VIOLATED'}"
        ]
        for check in self.checks:
            mark = "ok " if check.passed else "FAIL"
            lines.append(f"  [{mark}] {check.name}")
            if not check.passed:
                for key, value in sorted(check.details.items()):
                    lines.append(f"         {key}: {value}")
        counts: Dict[str, int] = {}
        for entry in self.injections:
            counts[entry["kind"]] = counts.get(entry["kind"], 0) + 1
        for kind in sorted(counts):
            lines.append(f"  injected {kind} x{counts[kind]}")
        for diag in self.diagnostics:
            detail = " ".join(f"{k}={v}" for k, v in sorted(diag.items()) if k != "tag")
            lines.append(f"  diagnostic [{diag.get('tag', '?')}] {detail}")
        return "\n".join(lines)


# ------------------------------------------------------------------ runner
class ChaosRunner:
    """Execute a workload under a `FaultPlan` and check the recovery invariants."""

    def __init__(
        self,
        plan: FaultPlan,
        registry: Optional[MetricsRegistry] = None,
        clock=None,
        trace_dir: Optional[str] = None,
        tracer: Optional[Tracer] = None,
    ):
        self.plan = plan
        # Every chaos run records a timeline: injections land as `chaos.*`
        # trace events, workload attempts/steps as spans. With a `trace_dir`
        # the recorder streams span JSONL there (and the supervised workload
        # inherits the dir through the env protocol), so `accelerate-tpu
        # trace dump` renders the sweep as one Perfetto timeline; without one
        # the in-memory ring still backs the trace_complete invariant.
        self.trace_dir = str(trace_dir) if trace_dir else None
        if tracer is None:
            tracer = Tracer(
                recorder=FlightRecorder(log_dir=self.trace_dir), category="chaos"
            )
        self.tracer = tracer
        self.session = ChaosSession(plan, registry=registry, clock=clock, tracer=tracer)

    # ---------------------------------------------------------------- train
    def run_train(
        self,
        base_dir: str,
        steps: int = 8,
        max_restarts: int = 16,
        keep_last_n: int = 3,
        downtime_budget_s: float = 5.0,
        async_save: bool = False,
        no_progress_threshold: int = 6,
    ) -> InvariantReport:
        """In-process supervised train loop: RegressionModel, one checkpoint per
        step, chaos polled at every boundary. An `InjectedKill` ends an attempt
        exactly like a SIGKILL ends a process (no cleanup runs in the workload);
        the runner then 'respawns' — fresh Accelerator, resume from latest —
        until the run completes or the restart budget is spent.

        `async_save=True` runs every save through the snapshot-then-commit
        background committer: a kill while a commit is in flight ABORTS the
        commit before 'respawning' (a dead process cannot publish), a committer
        that dies of an injected kill surfaces at the next step boundary
        exactly like a process death, and an ordinary commit failure (EIO
        retries exhausted) surfaces as `CheckpointCommitError` on the next
        save's barrier — counted as a crash, restarted, and the previously
        published checkpoint must still resolve.

        `no_progress_threshold`: after that many CONSECUTIVE restarts with no
        new independently-verified checkpoint published (the same step being
        killed over and over — e.g. an every-match `at_step` SIGKILL whose
        async commit can never publish), the runner stops sweeping and tags a
        ``crash_loop`` diagnostic instead of spending the whole restart budget
        on a deterministic livelock. The default leaves headroom for
        legitimate transient-fault storms (a several-retry EIO burst clears
        after a few fruitless restarts and must not be cut short); 0 disables
        the detector."""
        from ..checkpointing import CheckpointCommitError

        journal: Dict[str, Any] = {
            "attempts": 0, "graceful_exits": 0, "commit_failures": 0,
            "saves": [], "intents": [], "resumes": [],
        }
        ledger: Dict[str, float] = {}
        restarts = 0
        downtime_s = 0.0
        completed = False
        checkpoint_base = os.path.join(str(base_dir), "checkpoints")
        diagnostics: List[dict] = []
        last_progress = independent_latest_step(checkpoint_base)
        no_progress = 0
        boundary = StepBoundaryInjector(self.session, hard=False)
        with FilesystemInjector(self.session), HarnessInjector(self.session):
            while True:
                journal["attempts"] += 1
                attempt_span = self.tracer.start_span(
                    "train.attempt", category="train", attempt=journal["attempts"]
                )
                try:
                    with self.tracer.activate(attempt_span):
                        self._train_attempt(
                            base_dir, steps, keep_last_n, boundary, journal, ledger,
                            async_save=async_save,
                        )
                    attempt_span.annotate(outcome="completed").end()
                    completed = True
                    break
                except InjectedKill:
                    # hard kill: nothing in the attempt got to clean up. The
                    # crash boundary is a standalone event (streamed, were this
                    # a real process, BEFORE the respawn) — what the stitched
                    # timeline anchors the restart on.
                    attempt_span.annotate(outcome="killed").end()
                    self.tracer.event(
                        "chaos.crash_boundary", category="chaos",
                        attempt=journal["attempts"], kind="sigkill",
                    )
                except CheckpointCommitError:
                    # A failed (not killed) background commit surfaced at the
                    # barrier: production's train loop crashes on it and the
                    # supervisor restarts — the runner plays both parts.
                    journal["commit_failures"] += 1
                    attempt_span.annotate(outcome="commit_failed").end()
                    self.tracer.event(
                        "chaos.crash_boundary", category="chaos",
                        attempt=journal["attempts"], kind="commit_failure",
                    )
                except _GracefulPreemption:
                    attempt_span.annotate(outcome="preempted").end()
                    self.tracer.event(
                        "chaos.crash_boundary", category="chaos",
                        attempt=journal["attempts"], kind="sigterm",
                    )
                    journal["graceful_exits"] += 1
                restarts += 1
                if restarts > max_restarts:
                    break
                # No-forward-progress detection: a restart that resumes with
                # the SAME newest verified checkpoint as the last one made no
                # progress; K in a row is a livelock, not a recovery chain.
                progress = independent_latest_step(checkpoint_base)
                if progress == last_progress:
                    no_progress += 1
                else:
                    no_progress = 0
                last_progress = progress
                if no_progress_threshold and no_progress >= no_progress_threshold:
                    diagnostics.append({
                        "tag": "crash_loop",
                        "why": "no_forward_progress",
                        "restarts_without_new_checkpoint": no_progress,
                        "stuck_at_verified_step": progress,
                        "restarts": restarts,
                    })
                    logger.error(
                        "chaos: CRASH LOOP — %d consecutive restarts with no new "
                        "published checkpoint (stuck at verified step %s); stopping "
                        "the sweep. diagnostic=crash_loop",
                        no_progress, progress,
                    )
                    break
                backoff = min(0.01 * restarts, 0.05)
                self.session.clock.sleep(backoff)
                downtime_s += backoff
        checks = [
            self._check_resume_exactness(journal),
            self._check_no_torn_resolved(journal, checkpoint_base),
            self._check_restart_budget(completed, restarts, max_restarts, downtime_s,
                                       downtime_budget_s),
            self._check_ledger_reconciles(ledger, journal, async_save=async_save),
            self._check_trace_complete(journal),
        ]
        return self._report(
            "async-train" if async_save else "train", checks, diagnostics=diagnostics
        )

    def _train_attempt(
        self,
        base_dir: str,
        steps: int,
        keep_last_n: int,
        boundary: StepBoundaryInjector,
        journal: Dict[str, Any],
        ledger: Dict[str, float],
        async_save: bool = False,
    ):
        accelerator, model, opt, pdl = build_train_workload(
            base_dir, keep_last_n, self.plan.seed, async_save=async_save
        )
        handler = accelerator.register_preemption_checkpoint(exit_on_save=False)
        stream = None
        finished_cleanly = False
        try:
            manager = accelerator.checkpoint_manager()
            start_step = 0
            try:
                resolved = manager.resolve("latest")
            except FileNotFoundError:
                resolved = None
            if resolved is not None:
                accelerator.load_state("latest")
                evidence = resume_evidence(resolved, model, manager.base_dir)
                journal["resumes"].append({"attempt": journal["attempts"], **evidence})
                resumed_step = evidence["step"]
                start_step = (resumed_step if resumed_step is not None else -1) + 1
                self.tracer.event(
                    "train.resume", category="train",
                    attempt=journal["attempts"], step=resumed_step,
                )

            def batches():
                while True:
                    for b in pdl:
                        yield b

            stream = batches()
            for step in range(start_step, steps):
                with self.tracer.span("train.step", category="train", step=step):
                    batch = next(stream)
                    accelerator.backward(model.loss, batch)
                    opt.step()
                    opt.zero_grad()
                    digest = params_digest(model)
                    # Intent BEFORE the save: a kill after the directory rename
                    # but before save_state returns leaves a committed
                    # checkpoint the journal would otherwise not know the
                    # digest of.
                    intended_step = accelerator.save_iteration
                    journal["intents"].append(
                        {"step": intended_step, "digest": digest}
                    )
                    path = accelerator.save_state()
                    journal["saves"].append({
                        "attempt": journal["attempts"],
                        # An async save's manifest does not exist yet when
                        # save_state returns — the intended step is the record
                        # (the intent above already carries the same pair).
                        "step": intended_step if async_save else manifest_step(path),
                        "digest": digest,
                        "path": path,
                    })
                # Chaos fires AT the boundary, outside the step span: a kill
                # here models SIGKILL-between-steps, not a mid-step death.
                boundary.poll(step)
                # A background committer that died of an injected kill is a
                # process death: surface it at the boundary, like a SIGKILL.
                accelerator.poll_async_checkpoint()
                if handler.preemption_requested:
                    raise _GracefulPreemption()
            # A completed run's final commit must land (or surface its failure)
            # before the attempt is declared done.
            accelerator.drain_checkpoints()
            finished_cleanly = True
        finally:
            if stream is not None:
                # A kill mid-iteration leaves the loader generator suspended;
                # close it here instead of letting GC tear it down mid-suite.
                stream.close()
            if not finished_cleanly:
                # Process-death semantics for the background committer: a dead
                # process cannot publish. Abort the in-flight commit (it stops
                # at the next phase boundary, leaving only staging litter) and
                # join without raising — the attempt is already dying of the
                # original kill.
                accelerator.abort_async_checkpoint()
            for cause, seconds in accelerator.timeline.goodput()["lost_s"].items():
                ledger[cause] = ledger.get(cause, 0.0) + seconds
            commit_hist = getattr(accelerator, "_m_ckpt_commit_seconds", None)
            if commit_hist is not None and commit_hist.count:
                ledger["checkpoint_async_commit"] = (
                    ledger.get("checkpoint_async_commit", 0.0) + commit_hist.sum
                )
            handler.uninstall()

    # ---------------------------------------------------------------- supervised train
    def run_supervised_train(
        self,
        base_dir: str,
        steps: int = 5,
        max_restarts: int = 4,
        downtime_budget_s: float = 30.0,
        async_save: bool = False,
        no_progress_threshold: int = 6,
        mesh_2d: bool = False,
    ) -> InvariantReport:
        """The end-to-end path: the real `Supervisor` restarting a real
        subprocess workload (`python -m accelerate_tpu.chaos.workload`), the
        plan propagated through ``ACCELERATE_TPU_FAULT_PLAN`` exactly as
        `accelerate-tpu launch --fault_plan` would. With `async_save` the
        workload saves through the background committer and a `proc.sigkill`
        is a REAL SIGKILL — a commit genuinely in flight dies mid-write, the
        strongest form of the kill-during-background-commit sweep."""
        from ..fault_tolerance import PREEMPTED_EXIT_CODE, Supervisor

        base_dir = str(base_dir)
        os.makedirs(base_dir, exist_ok=True)
        plan_path = self.plan.save(os.path.join(base_dir, "fault_plan.json"))
        env = dict(os.environ)
        env[FAULT_PLAN_ENV] = plan_path
        env.setdefault("JAX_PLATFORMS", "cpu")
        cmd = [
            sys.executable, "-m", "accelerate_tpu.chaos.workload",
            "--base-dir", base_dir, "--steps", str(steps),
        ] + (["--async-save"] if async_save else []) + (
            ["--mesh-2d"] if mesh_2d else []
        )
        # A clean preemption handoff (exit 143) ENDS supervision by design —
        # in production the scheduler respawns the whole job. The runner plays
        # the scheduler: re-run the supervisor after each handoff (counted
        # against the same budget) until the workload completes or fails.
        restarts = 0
        preemption_handoffs = 0
        downtime_s = 0.0
        crash_loop = False
        crash_loop_reason = None
        checkpoint_base = os.path.join(base_dir, "checkpoints")
        while True:
            supervisor = Supervisor(
                cmd,
                env=env,
                max_restarts=max_restarts - restarts,
                grace_period=30.0,
                backoff_seconds=0.05,
                max_backoff_seconds=0.2,
                monitor_interval=0.05,
                crash_loop_min_uptime=0.0,  # every attempt imports jax; uptime is not a crash signal here
                # No-forward-progress detection: each subprocess attempt
                # re-arms the plan from env, so an every-attempt at_step kill
                # whose (async) checkpoint can never publish would otherwise
                # re-kill the SAME step until the budget burns — the newest
                # independently-verified checkpoint is the progress token.
                # Same headroom rationale as run_train's default: a transient
                # fault storm may burn a few attempts before the first publish
                # and must not be cut short.
                progress_fn=lambda: independent_latest_step(checkpoint_base),
                no_progress_threshold=no_progress_threshold,
                # Attempt spans + trace-context injection: each child re-arms
                # via Tracer.from_env and parents its spans under the attempt
                # that spawned it — the restart chain stitches into ONE trace.
                tracer=self.tracer,
            )
            code = supervisor.run()
            restarts += supervisor.restart_count
            downtime_s += supervisor.downtime_s
            crash_loop = crash_loop or supervisor.crash_loop_detected
            crash_loop_reason = crash_loop_reason or supervisor.crash_loop_reason
            if supervisor.crash_loop_detected:
                break
            if code == PREEMPTED_EXIT_CODE and preemption_handoffs + restarts < max_restarts:
                preemption_handoffs += 1
                continue
            break
        journal = self._read_workload_journal(base_dir)
        diagnostics: List[dict] = []
        if crash_loop:
            diagnostics.append({
                "tag": "crash_loop",
                "why": crash_loop_reason or "unknown",
                "restarts": restarts,
                "stuck_at_verified_step": independent_latest_step(checkpoint_base),
            })
        checks = [
            self._check_resume_exactness(journal),
            self._check_no_torn_resolved(journal, checkpoint_base),
            InvariantCheck(
                "supervisor",
                passed=code == 0 and restarts + preemption_handoffs <= max_restarts
                and downtime_s <= downtime_budget_s,
                details={
                    "exit_code": code,
                    "restarts": restarts,
                    "preemption_handoffs": preemption_handoffs,
                    "max_restarts": max_restarts,
                    "downtime_s": round(downtime_s, 6),
                    "downtime_budget_s": downtime_budget_s,
                    "crash_loop_detected": crash_loop,
                    "crash_loop_reason": crash_loop_reason,
                },
            ),
        ]
        # The workload's own injections happened in child processes; fold its
        # journal into ours so the report still carries them.
        if mesh_2d:
            checks.append(self._check_zero_state_sharded(journal))
        for entry in journal.get("injections", []):
            self.session.injections.append(entry)
            self.session.registry.counter(
                "chaos_injected_total",
                help="faults injected by the chaos subsystem, by kind",
                labels={"kind": entry["kind"]},
            ).inc()
        checks.append(self._check_trace_complete(journal, supervised=True))
        return self._report("supervised-train", checks, diagnostics=diagnostics)

    @staticmethod
    def _read_workload_journal(base_dir: str) -> Dict[str, Any]:
        journal: Dict[str, Any] = {
            "attempts": 0, "graceful_exits": 0, "saves": [], "intents": [],
            "resumes": [], "injections": [], "layouts": [],
        }
        path = os.path.join(str(base_dir), "chaos_journal.jsonl")
        if not os.path.isfile(path):
            return journal
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    record = json.loads(line)
                except json.JSONDecodeError:
                    continue  # a torn final line from a killed writer
                rtype = record.pop("type", None)
                if rtype == "attempt":
                    journal["attempts"] += 1
                elif rtype == "graceful_exit":
                    journal["graceful_exits"] += 1
                elif rtype in ("save", "intent", "resume", "injection", "layout"):
                    journal[rtype + "s"].append(record)
        return journal

    # ---------------------------------------------------------------- serve
    def run_serve(
        self,
        num_requests: int = 8,
        num_slots: int = 2,
        chunk_size: int = 4,
        max_queue: int = 4,
        max_new_tokens: int = 4,
        max_cycles: int = 200,
        speculative: bool = False,
        attention_impl: str = "xla",
        kv_cache_dtype: str = "bf16",
        tp: int = 1,
    ) -> InvariantReport:
        """Serving workload: a tiny llama `ContinuousBatcher` fed one request
        per cycle (plus scripted queue bursts), driven to drain under injected
        dispatch stalls/failures. Chaos shares the engine's metrics registry so
        the report's snapshot carries both. `speculative=True` runs the same
        sweeps through the draft/verify chunk (draft window in every admission,
        history mirror in every blast-radius rebuild), so recovery is proven to
        reconstruct the speculative state too. `attention_impl="pallas_paged"`
        drives the sweeps through the fused page-walk kernels
        (ops/paged_attention): blast-radius recovery must rebuild the
        kernel-path executables identically — same invariants, no retrace.
        `kv_cache_dtype="int8"`/`"fp8_e4m3"` runs the sweeps on the QUANTIZED
        page pool: the blast-radius rebuild must recreate the quantized pools
        AND their scale pools from zeros, and the page ledger must still
        close — fault paths exercise the quantized cache, not just happy
        decode. `tp=N` spans the engine over an N-device submesh: the same
        sweeps must leave the rebuilt pools (and scale pools) SHARDED on
        that submesh — the extra `tp_pool_sharded` invariant fails if a
        blast-radius recovery quietly rebuilt them replicated."""
        from ..models.llama import LlamaConfig, create_llama_model
        from ..serving import FINISH_REASONS, ContinuousBatcher, QueueFull, Request

        cfg = LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=64,
            rope_theta=10000.0,
        )
        model = create_llama_model(cfg, seq_len=32)
        # page_size=4 with a shared 8-token system prompt on half the traffic,
        # so the dispatch-failure sweeps exercise page refcounts AND live
        # prefix registrations — the page-ledger invariant below is
        # non-vacuous.
        engine = ContinuousBatcher(
            model, num_slots=num_slots, max_length=64, chunk_size=chunk_size,
            max_queue=max_queue, registry=self.session.registry,
            tracer=self.tracer, page_size=4,
            speculative=speculative, draft_tokens=3,
            attention_impl=attention_impl, kv_cache_dtype=kv_cache_dtype,
            tp=tp,
        )
        ServingInjector(self.session).arm(engine)
        rng = np.random.default_rng(self.plan.seed)
        shared_prefix = rng.integers(1, cfg.vocab_size, (8,)).astype(np.int32)

        next_id = 0
        rejected = 0
        accepted: List[int] = []
        first_id_after_error: Optional[int] = None

        def make_request() -> Request:
            nonlocal next_id
            prompt = rng.integers(1, cfg.vocab_size, (int(rng.integers(2, 9)),)).astype(np.int32)
            if rng.integers(2):
                prompt = np.concatenate([shared_prefix, prompt])
            request = Request(next_id, prompt, max_new_tokens=max_new_tokens)
            next_id += 1
            return request

        def submit_one() -> bool:
            nonlocal rejected
            request = make_request()
            try:
                engine.submit(request)
            except QueueFull:
                rejected += 1
                return False
            accepted.append(request.request_id)
            return True

        # After a dispatch failure's blast radius, the recovery invariant needs
        # live evidence: keep the workload submitting a couple of fresh probe
        # requests past the failure so "the engine still serves" is observed,
        # not assumed.
        error_kinds = ("serve.dispatch_error", "serve.insert_error")
        recovery_probes = 2 if any(ev.kind in error_kinds for ev in self.plan.events) else 0
        probes_sent = 0
        errors_before = 0
        cycles = 0
        stalled = False
        while (
            len(accepted) < num_requests
            or engine.pending
            or (first_id_after_error is not None and probes_sent < recovery_probes)
        ):
            if cycles >= max_cycles:
                stalled = True
                break
            if len(accepted) < num_requests:
                submit_one()
            elif first_id_after_error is not None and probes_sent < recovery_probes:
                if submit_one():
                    probes_sent += 1
            for ev in self.session.fire("serve.queue_burst", step=cycles):
                for _ in range(int(ev.args.get("count", 8))):
                    submit_one()
            engine.step()
            error_count = sum(
                1 for e in self.session.injections if e["kind"] in error_kinds
            )
            if error_count > errors_before and first_id_after_error is None:
                first_id_after_error = next_id
            errors_before = error_count
            cycles += 1
        results = dict(engine.drain())
        engine.close()

        finish_reasons = {
            rid: results[rid].finish_reason if rid in results else None for rid in accepted
        }
        non_terminal = {
            rid: reason for rid, reason in finish_reasons.items()
            if reason not in FINISH_REASONS
        }
        checks = [
            InvariantCheck(
                "terminal_finish_reasons",
                passed=not non_terminal and not stalled,
                details={
                    "accepted": len(accepted), "rejected_queue_full": rejected,
                    "non_terminal": non_terminal, "stalled": stalled, "cycles": cycles,
                },
            ),
            InvariantCheck(
                "queue_bounded",
                passed=int(engine.stats["queue_peak"]) <= max_queue,
                details={"queue_peak": int(engine.stats["queue_peak"]), "max_queue": max_queue},
            ),
            self._check_engine_recovered(finish_reasons, first_id_after_error),
            self._check_serve_ledger(engine, accepted),
            self._check_page_ledger(engine),
            self._check_serve_trace(accepted),
        ]
        if tp > 1:
            checks.append(self._check_tp_pool_sharded(engine, tp))
        return self._report("serve", checks)

    def _check_tp_pool_sharded(self, engine, tp: int) -> InvariantCheck:
        """Mesh-spanning engines: the LIVE slot cache — including one rebuilt
        by a blast-radius recovery mid-sweep — must still be sharded over the
        `tp`-device submesh (K/V pools and quantized scale pools carry the
        "model" axis; a silently-replicated rebuild would serve correctly
        while spending N x the HBM, which is exactly the failure chaos is
        here to catch)."""
        import jax

        unsharded = []
        sharded = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(engine._cache)[0]:
            name = str(getattr(path[-1], "key", getattr(path[-1], "name", path[-1])))
            if name not in ("cached_key", "cached_value", "key_scale", "value_scale"):
                continue
            spec = getattr(getattr(leaf, "sharding", None), "spec", None)
            if spec is None or "model" not in tuple(spec):
                unsharded.append("/".join(str(getattr(k, "key", k)) for k in path))
            else:
                sharded += 1
        mesh_ok = engine.mesh is not None and engine.mesh.devices.size == tp
        return InvariantCheck(
            "tp_pool_sharded",
            passed=mesh_ok and sharded > 0 and not unsharded,
            details={
                "tp": tp, "mesh_devices": int(engine.mesh.devices.size) if engine.mesh else 0,
                "sharded_leaves": sharded, "unsharded_leaves": unsharded,
            },
        )

    # ---------------------------------------------------------------- router
    def run_router(
        self,
        num_requests: int = 12,
        replicas: int = 3,
        num_slots: int = 2,
        chunk_size: int = 4,
        max_queue: int = 8,
        max_new_tokens: int = 4,
        max_cycles: int = 400,
        hedge_after_s: Optional[float] = None,
    ) -> InvariantReport:
        """Replicated-fleet workload: a `router.Router` over N in-process
        engines fed one request per cycle, driven to drain while the
        `RouterInjector` kills / stalls / poisons individual replicas
        mid-traffic. The machine-checked invariants:

          - **terminal_finish_reasons** — every accepted request reaches a
            terminal reason from `ROUTER_FINISH_REASONS` (``replica_lost``
            included) and the workload drains without stalling;
          - **no_duplicate_streams** — the concatenation of every stream event
            the router forwarded for a request equals that request's final
            token list EXACTLY (a retried or hedged request can never deliver
            a token twice);
          - **fleet_recovered** — requests submitted AFTER the first injected
            replica fault still complete normally, and a killed replica is
            back in a routable state by drain;
          - **no_route_to_ejected** — the routing journal contains no decision
            that placed work on a replica while it was ejected (or draining);
          - **ledger_reconciles** — chaos counters match the injection journal
            and `router_retries_total` matches the routing journal's retries.
        """
        from ..models.llama import LlamaConfig, create_llama_model
        from ..router import ROUTER_FINISH_REASONS, Router
        from ..serving import QueueFull, Request

        cfg = LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=64,
            rope_theta=10000.0,
        )
        model = create_llama_model(cfg, seq_len=32)
        router = Router(
            model, replicas=replicas, num_slots=num_slots, max_length=64,
            chunk_size=chunk_size, max_queue=max_queue, default_deadline_s=60.0,
            hedge_after_s=hedge_after_s, registry=self.session.registry,
            tracer=self.tracer, page_size=4,
            rejoin_cooldown_s=0.05, probation_steps=2, stall_degrade_s=None,
        )
        RouterInjector(self.session).arm(router)
        rng = np.random.default_rng(self.plan.seed)

        next_id = 0
        rejected = 0
        accepted: List[int] = []
        streamed: Dict[int, List[int]] = {}
        first_id_after_fault: Optional[int] = None

        def submit_one() -> bool:
            nonlocal next_id, rejected
            prompt = rng.integers(1, cfg.vocab_size, (int(rng.integers(2, 9)),)).astype(np.int32)
            request = Request(next_id, prompt, max_new_tokens=max_new_tokens)
            next_id += 1
            try:
                router.submit(request)
            except QueueFull:
                rejected += 1
                return False
            accepted.append(request.request_id)
            streamed[request.request_id] = []
            return True

        router_kinds = ("router.replica_kill", "router.replica_stall", "router.replica_poison")
        fault_planned = any(ev.kind in router_kinds for ev in self.plan.events)
        recovery_probes = 3 if fault_planned else 0
        probes_sent = 0
        faults_before = 0
        cycles = 0
        stalled = False
        while (
            len(accepted) < num_requests
            or router.pending
            or (first_id_after_fault is not None and probes_sent < recovery_probes)
        ):
            if cycles >= max_cycles:
                stalled = True
                break
            if len(accepted) < num_requests:
                submit_one()
            elif first_id_after_fault is not None and probes_sent < recovery_probes:
                if submit_one():
                    probes_sent += 1
            for ev in self.session.fire("serve.queue_burst", step=cycles):
                for _ in range(int(ev.args.get("count", 8))):
                    submit_one()
            for rid, toks in router.step():
                if rid in streamed:
                    streamed[rid].extend(toks)
            fault_count = sum(
                1 for e in self.session.injections if e["kind"] in router_kinds
            )
            if fault_count > faults_before and first_id_after_fault is None:
                first_id_after_fault = next_id
            faults_before = fault_count
            cycles += 1
        results = dict(router.drain())
        # Recovery phase: a replica killed late in the run is still inside its
        # rejoin cooldown when the traffic drains — keep cycling (bounded)
        # until the health machine brings every replica back, so
        # `fleet_recovered` measures actual recovery, not drain timing.
        while (
            any(s == "ejected" for s in router.replica_states.values())
            and cycles < max_cycles
        ):
            self.session.clock.sleep(0.01)
            router.step()
            cycles += 1
        for _ in range(router.replica_set.probation_steps + 1):
            router.step()
        final_states = dict(router.replica_states)
        routing_log = list(router.routing_log)
        state_log = list(router.replica_set.state_log)
        retries_counter = int(router.stats["retries"])
        router.close()

        finish_reasons = {
            rid: results[rid].finish_reason if rid in results else None for rid in accepted
        }
        non_terminal = {
            rid: reason for rid, reason in finish_reasons.items()
            if reason not in ROUTER_FINISH_REASONS
        }
        duplicate_streams = {
            rid: {"streamed": streamed[rid], "result": list(results[rid].tokens)}
            for rid in accepted
            if rid in results and streamed[rid] != list(results[rid].tokens)
        }
        checks = [
            InvariantCheck(
                "terminal_finish_reasons",
                passed=not non_terminal and not stalled,
                details={
                    "accepted": len(accepted), "rejected_queue_full": rejected,
                    "non_terminal": non_terminal, "stalled": stalled, "cycles": cycles,
                    "reasons": _reason_counts(finish_reasons),
                },
            ),
            InvariantCheck(
                "no_duplicate_streams",
                passed=not duplicate_streams,
                details={"mismatched": duplicate_streams},
            ),
            self._check_fleet_recovered(
                finish_reasons, first_id_after_fault, final_states, fault_planned
            ),
            self._check_no_route_to_ejected(routing_log, state_log),
            self._check_router_ledger(routing_log, retries_counter, accepted, finish_reasons),
        ]
        return self._report("router", checks)

    # ---------------------------------------------------------------- fleet
    def run_fleet(
        self,
        num_requests: int = 10,
        replicas: int = 2,
        num_slots: int = 2,
        chunk_size: int = 4,
        max_queue: int = 8,
        max_new_tokens: int = 4,
        max_cycles: int = 2000,
        autoscale: bool = True,
        step_timeout_s: float = 15.0,
        workdir: Optional[str] = None,
        transport: str = "pipe",
        reconnect_deadline_s: float = 8.0,
    ) -> InvariantReport:
        """Out-of-process fleet workload: a `Router` over REAL subprocess
        engine workers (`worker.SubprocessEngine` via `make_subprocess_factory`)
        driven to drain while the env-propagated plan SIGKILLs and stalls the
        worker PROCESSES themselves mid-traffic. The PR 10 router invariants
        are re-checked against true process fault domains, plus two new ones:

          - **worker_restart_rejoins_warm** — every observed worker death (pid
            change on a replica) was followed by a respawned process whose
            ready handshake reports a pre-warmed insert ladder, and the fleet
            ends with every non-retired replica routable;
          - **autoscaler_converges** (``autoscale=True``) — the queue-burst
            pressure scales the fleet up past its floor, and after the traffic
            drains the autoscaler retires the extra workers back to the floor.

        With ``transport="socket"`` the workers serve over TCP and the plan may
        carry ``net.*`` faults (injected controller-side at the transport seam
        via `TransportInjector`), adding two network invariants:

          - **reconnect_reconciles** — the controller's successful-reconnect
            counters are fully accounted by the workers' re-registration
            journal (every reconnect the controller counted, some worker
            accepted under a bumped epoch);
          - **partition_is_not_death** — a healed partition must NOT change any
            worker's pid (reconnect, not respawn); only a partition window
            past ``reconnect_deadline_s`` may escalate to the respawn path,
            and then it MUST.

        Worker-side injections are journaled (append+fsync, BEFORE the kill
        lands) to a shared journal the ledger invariant reconciles against
        observed process deaths — and that restarted workers read back so a
        re-armed plan cannot livelock by re-killing at the same trigger."""
        import tempfile

        from ..models.llama import LlamaConfig, create_llama_model
        from ..router import ROUTER_FINISH_REASONS, Router
        from ..serving import QueueFull, Request
        from ..worker import CHAOS_JOURNAL_ENV, make_subprocess_factory
        from .injectors import TransportInjector
        from .plan import FAULT_PLAN_ENV

        net_kinds = ("net.partition", "net.slow", "net.flap")
        net_events = [ev for ev in self.plan.events if ev.kind in net_kinds]
        if net_events and transport != "socket":
            raise ValueError(
                "net.* faults inject at the socket-transport seam: run the "
                "fleet workload with transport='socket' (the pipe transport "
                "has no reconnectable link to partition)"
            )

        cfg = LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=64,
            rope_theta=10000.0,
        )
        model = create_llama_model(cfg, seq_len=32)
        workdir = workdir or tempfile.mkdtemp(prefix="accelerate_tpu_chaos_fleet_")
        journal_path = os.path.join(workdir, "fleet_chaos_journal.jsonl")
        worker_env = dict(os.environ)
        worker_env[FAULT_PLAN_ENV] = self.plan.to_json(indent=None)
        worker_env[CHAOS_JOURNAL_ENV] = journal_path
        if self.trace_dir:
            worker_env["ACCELERATE_TPU_TRACE_DIR"] = self.trace_dir
        factory = make_subprocess_factory(
            model,
            engine_kwargs=dict(
                num_slots=num_slots, max_length=64, chunk_size=chunk_size,
                max_queue=max_queue, page_size=4,
            ),
            workdir=workdir, env=worker_env, step_timeout_s=step_timeout_s,
            transport=transport,
            reconnect_deadline_s=(
                reconnect_deadline_s if transport == "socket" else None
            ),
        )
        router = Router(
            model, replicas=replicas, max_queue=max_queue, default_deadline_s=120.0,
            registry=self.session.registry, tracer=self.tracer,
            engine_factory=factory,
            rejoin_cooldown_s=0.05, probation_steps=2, stall_degrade_s=None,
            heartbeat_timeout_s=None,  # hang detection is the client step timeout
            **(dict(
                min_replicas=replicas, max_replicas=replicas + 1,
                autoscale_queue_high=1.5, autoscale_cooldown_s=0.0,
                idle_retire_s=0.05,
            ) if autoscale else {}),
        )
        if net_events:
            # Net faults damage the controller-side transport seam (sever the
            # link, delay/tear frames) — arm the wrapper on every engine the
            # router builds, including respawns.
            TransportInjector(self.session).arm(router)
        rng = np.random.default_rng(self.plan.seed)

        next_id = 0
        rejected = 0
        accepted: List[int] = []
        streamed: Dict[int, List[int]] = {}
        first_id_after_fault: Optional[int] = None
        #: replica index -> [(pid, warm_handshake)] in observation order.
        pids_seen: Dict[int, List[tuple]] = {}
        peak_active = router.active_replicas

        def observe_fleet():
            nonlocal peak_active
            peak_active = max(peak_active, router.active_replicas)
            for replica in router.replica_set.replicas:
                if replica.dead or replica.state == "retired":
                    continue
                engine = replica.engine
                pid = getattr(engine, "pid", None)
                seen = pids_seen.setdefault(replica.index, [])
                if pid is not None and (not seen or seen[-1][0] != pid):
                    ready = getattr(engine, "ready_info", {}) or {}
                    seen.append((pid, bool(ready.get("warm"))))

        def submit_one() -> bool:
            nonlocal next_id, rejected
            prompt = rng.integers(1, cfg.vocab_size, (int(rng.integers(2, 9)),)).astype(np.int32)
            request = Request(next_id, prompt, max_new_tokens=max_new_tokens)
            next_id += 1
            try:
                router.submit(request)
            except QueueFull:
                rejected += 1
                return False
            accepted.append(request.request_id)
            streamed[request.request_id] = []
            return True

        fleet_kinds = ("fleet.worker_kill", "fleet.worker_stall")
        planned_faults = sum(
            max(ev.times, 1) for ev in self.plan.events if ev.kind in fleet_kinds
        )
        planned_net = sum(max(ev.times, 1) for ev in net_events)
        #: A partition/flap window longer than the reconnect budget MUST
        #: escalate to the respawn path; anything shorter must heal in place.
        _net_windows = {"net.partition": 0.5, "net.flap": 0.1}
        escalation_expected = any(
            ev.kind in _net_windows
            and float(ev.args.get("window_s", _net_windows[ev.kind]))
            > reconnect_deadline_s
            for ev in net_events
        )
        fault_planned = (planned_faults + planned_net) > 0
        recovery_probes = 3 if fault_planned else 0
        #: Worker faults fire IN the workers (env-propagated plan, their own
        #: step-op call counts) and are journaled BEFORE the damage lands, so
        #: the journal — not a controller-side proxy like ejection counts,
        #: which a flapping rejoin could inflate — is the ground truth for
        #: "every planned fault actually fired". Traffic keeps flowing
        #: (bounded) until it says so; a sweep that never exercised its
        #: faults must go red, not green.
        hard_cap = max(num_requests * 8, num_requests + 32)
        planned_total = planned_faults + planned_net

        def faults_landed() -> int:
            # Worker faults land in the worker journal; net faults fire
            # controller-side at the transport seam and land in the session's
            # own injection counters.
            worker_side = sum(
                1 for e in self._read_fleet_journal(journal_path)
                if e.get("kind") in fleet_kinds
            )
            counts = self.session.counts()
            net_side = sum(counts.get(kind, 0) for kind in net_kinds)
            return worker_side + net_side

        probes_sent = 0
        faults_before = 0
        cycles = 0
        stalled = False
        observe_fleet()
        while (
            len(accepted) < num_requests
            or router.pending
            or (fault_planned and faults_landed() < planned_total
                and len(accepted) < hard_cap)
            or (first_id_after_fault is not None and probes_sent < recovery_probes)
        ):
            if cycles >= max_cycles:
                stalled = True
                break
            if len(accepted) < num_requests:
                submit_one()
            elif (
                fault_planned and faults_landed() < planned_total
                and len(accepted) < hard_cap
            ):
                submit_one()  # sustain pressure until every planned fault lands
            elif first_id_after_fault is not None and probes_sent < recovery_probes:
                if submit_one():
                    probes_sent += 1
            for ev in self.session.fire("serve.queue_burst", step=cycles):
                for _ in range(int(ev.args.get("count", 8))):
                    submit_one()
            for rid, toks in router.step():
                if rid in streamed:
                    streamed[rid].extend(toks)
            observe_fleet()
            landed = faults_landed()
            if landed > faults_before and first_id_after_fault is None:
                first_id_after_fault = next_id
            faults_before = landed
            cycles += 1
        results = dict(router.drain())
        # Recovery phase: cycle until every fault has run its course — a
        # replica mid-reconnect either heals in place or exhausts its budget
        # and escalates to a death, and every ejected replica rejoins (the
        # respawn path) — then until the autoscaler converged back to its
        # floor. Traffic that drains faster than `reconnect_deadline_s` must
        # not end the run with a reconnect still undecided: the verdict would
        # then depend on how long the workers took to compile.
        while (
            any(
                r.state in ("ejected", "reconnecting")
                or getattr(r.engine, "reconnecting", False)
                for r in router.replica_set.replicas
            )
            and cycles < max_cycles
        ):
            self.session.clock.sleep(0.01)
            router.step()
            observe_fleet()
            cycles += 1
        for _ in range(router.replica_set.probation_steps + 1):
            router.step()
        while (
            autoscale
            and router.active_replicas > router.min_replicas
            and cycles < max_cycles
        ):
            self.session.clock.sleep(0.01)
            router.step()
            cycles += 1
        observe_fleet()
        final_states = dict(router.replica_states)
        final_active = router.active_replicas
        scale_ups = int(router.stats.get("autoscale", {}).get("scale_ups", 0))
        scale_downs = int(router.stats.get("autoscale", {}).get("scale_downs", 0))
        routing_log = list(router.routing_log)
        state_log = list(router.replica_set.state_log)
        retries_counter = int(router.stats["retries"])
        # Successful reconnects live in the registry (memoized per replica
        # label), so the count survives engine rebuilds mid-sweep.
        reconnects_total = int(sum(
            inst.value for inst in self.session.registry.instruments()
            if inst.name == "router_reconnects_total"
        ))
        router.close()

        journal = self._read_fleet_journal(journal_path)
        finish_reasons = {
            rid: results[rid].finish_reason if rid in results else None for rid in accepted
        }
        non_terminal = {
            rid: reason for rid, reason in finish_reasons.items()
            if reason not in ROUTER_FINISH_REASONS
        }
        duplicate_streams = {
            rid: {"streamed": streamed[rid], "result": list(results[rid].tokens)}
            for rid in accepted
            if rid in results and streamed[rid] != list(results[rid].tokens)
        }
        # `fleet_recovered` must ignore retired replicas: the autoscaler
        # retiring its extra worker after the ramp is convergence, not failure.
        recovery_states = {i: s for i, s in final_states.items() if s != "retired"}
        checks = [
            InvariantCheck(
                "terminal_finish_reasons",
                passed=not non_terminal and not stalled,
                details={
                    "accepted": len(accepted), "rejected_queue_full": rejected,
                    "non_terminal": non_terminal, "stalled": stalled, "cycles": cycles,
                    "reasons": _reason_counts(finish_reasons),
                },
            ),
            InvariantCheck(
                "no_duplicate_streams",
                passed=not duplicate_streams,
                details={"mismatched": duplicate_streams},
            ),
            self._check_fleet_recovered(
                finish_reasons, first_id_after_fault, recovery_states, fault_planned
            ),
            self._check_no_route_to_ejected(routing_log, state_log),
            # A healed partition must not demand a death, so only worker-side
            # fleet faults (or a partition past the reconnect budget) put the
            # warm-restart check into its strict deaths>=1 mode.
            self._check_worker_restart_warm(
                pids_seen, journal, planned_faults > 0 or escalation_expected
            ),
            self._check_fleet_ledger(
                journal, pids_seen, routing_log, retries_counter, accepted,
                finish_reasons, planned_faults,
            ),
        ]
        if net_events:
            checks.append(self._check_reconnect_reconciles(
                reconnects_total, journal, planned_net,
                escalation_expected=escalation_expected,
            ))
            checks.append(self._check_partition_not_death(
                pids_seen, journal, reconnects_total,
                escalation_expected=escalation_expected,
                fleet_planned=planned_faults > 0,
                reconnect_deadline_s=reconnect_deadline_s,
            ))
        if autoscale:
            checks.append(InvariantCheck(
                "autoscaler_converges",
                passed=scale_ups >= 1 and peak_active > router.min_replicas
                and final_active == router.min_replicas and scale_downs >= 1,
                details={
                    "scale_ups": scale_ups, "scale_downs": scale_downs,
                    "peak_active": peak_active, "final_active": final_active,
                    "min_replicas": router.min_replicas,
                    "max_replicas": router.max_replicas,
                },
            ))
        return self._report("fleet", checks)

    @staticmethod
    def _read_fleet_journal(path: str) -> List[dict]:
        if not os.path.exists(path):
            return []
        entries = []
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    entries.append(json.loads(line))
                except json.JSONDecodeError:
                    continue  # torn tail of a SIGKILLed writer
        return entries

    @staticmethod
    def _check_worker_restart_warm(
        pids_seen: Dict[int, List[tuple]],
        journal: List[dict],
        fault_planned: bool,
    ) -> InvariantCheck:
        """Every worker death must be followed by a respawn whose ready
        handshake reports a pre-warmed engine — a restarted worker rejoins the
        fleet WARM, never paying a compile on the serving path."""
        deaths = sum(max(len(v) - 1, 0) for v in pids_seen.values())
        cold_rejoins = {
            index: [pid for pid, warm in seen[1:] if not warm]
            for index, seen in pids_seen.items()
            if any(not warm for _pid, warm in seen[1:])
        }
        if not fault_planned:
            return InvariantCheck(
                "worker_restart_rejoins_warm",
                passed=not cold_rejoins,
                details={"note": "no fleet fault in plan", "deaths": deaths},
            )
        return InvariantCheck(
            "worker_restart_rejoins_warm",
            passed=deaths >= 1 and not cold_rejoins,
            details={
                "observed_deaths": deaths,
                "cold_rejoins": cold_rejoins,
                "pids_per_replica": {
                    i: [pid for pid, _warm in seen] for i, seen in pids_seen.items()
                },
                "journaled_faults": len(journal),
            },
        )

    @staticmethod
    def _check_reconnect_reconciles(
        reconnects_total: int,
        journal: List[dict],
        planned_net: int,
        *,
        escalation_expected: bool = False,
    ) -> InvariantCheck:
        """Controller reconnect counters must reconcile against the workers'
        re-registration journal: every reconnect the controller counted was a
        registration some worker accepted under a bumped epoch (journaled
        worker-side as ``net.reregister`` before the ready frame goes out).
        The journal may run AHEAD of the counter — a handshake that lands but
        tears again during stream reconciliation is journaled by the worker
        yet never counted by the controller — but it can never run behind.
        And unless every planned net fault was an escalation (a window past
        the reconnect budget, where respawn — not reconnect — is the correct
        outcome), at least one reconnect must actually have happened."""
        reregisters = sum(1 for e in journal if e.get("kind") == "net.reregister")
        return InvariantCheck(
            "reconnect_reconciles",
            passed=(
                reregisters >= reconnects_total
                and (reconnects_total >= 1 or escalation_expected)
            ),
            details={
                "controller_reconnects": reconnects_total,
                "journaled_reregisters": reregisters,
                "planned_net_faults": planned_net,
                "escalation_expected": escalation_expected,
            },
        )

    @staticmethod
    def _check_partition_not_death(
        pids_seen: Dict[int, List[tuple]],
        journal: List[dict],
        reconnects_total: int,
        *,
        escalation_expected: bool,
        fleet_planned: bool,
        reconnect_deadline_s: float,
    ) -> InvariantCheck:
        """A healed partition must NOT change any worker's pid: the link
        reconnects and the stream resumes, the process is never respawned.
        Deaths caused by the plan's own worker-side faults (kills, stalls the
        step timeout escalates) are subtracted out; whatever remains is
        attributable to the network — and must be zero unless some partition
        window exceeded ``reconnect_deadline_s``, in which case the budget
        MUST have escalated to at least one respawn."""
        deaths = sum(max(len(v) - 1, 0) for v in pids_seen.values())
        fleet_deaths_budget = sum(
            1 for e in journal
            if e.get("kind") in ("fleet.worker_kill", "fleet.worker_stall")
        )
        net_deaths = deaths if not fleet_planned else max(
            0, deaths - fleet_deaths_budget
        )
        passed = net_deaths >= 1 if escalation_expected else net_deaths == 0
        return InvariantCheck(
            "partition_is_not_death",
            passed=passed,
            details={
                "observed_deaths": deaths,
                "fleet_fault_deaths_budget": fleet_deaths_budget,
                "net_attributed_deaths": net_deaths,
                "escalation_expected": escalation_expected,
                "reconnect_deadline_s": reconnect_deadline_s,
                "controller_reconnects": reconnects_total,
                "pids_per_replica": {
                    i: [pid for pid, _warm in seen] for i, seen in pids_seen.items()
                },
            },
        )

    def _check_fleet_ledger(
        self,
        journal: List[dict],
        pids_seen: Dict[int, List[tuple]],
        routing_log: List[dict],
        retries_counter: int,
        accepted: List[int],
        finish_reasons: Dict[int, Optional[str]],
        planned_faults: int = 0,
    ) -> InvariantCheck:
        """Reconcile three independent records: the controller-side injection
        counters, the worker-side chaos journal (written before each fault
        landed), and the observed process deaths. Every journaled kill must
        correspond to a real death of that worker's process, and the retry
        counter must match the routing journal exactly."""
        counts = self.session.counts()
        registry_ok = all(
            self.session.registry.value("chaos_injected_total", {"kind": kind}) == count
            for kind, count in counts.items()
        )
        journaled_kills: Dict[str, int] = {}
        for entry in journal:
            if entry.get("kind") == "fleet.worker_kill":
                worker = entry.get("worker", "?")
                journaled_kills[worker] = journaled_kills.get(worker, 0) + 1
        deaths_by_worker = {
            f"worker_{index}": max(len(seen) - 1, 0) for index, seen in pids_seen.items()
        }
        kills_unaccounted = {
            worker: count for worker, count in journaled_kills.items()
            if deaths_by_worker.get(worker, 0) < count
        }
        journal_retries = sum(1 for e in routing_log if e["kind"] == "retry")
        finished_total = sum(1 for r in finish_reasons.values() if r is not None)
        # Every PLANNED worker fault must actually have fired (journaled by the
        # worker before its damage): a sweep whose triggers never armed — the
        # workload drained too fast, a path_pattern matched nothing — must go
        # red, not silently pass with unexercised faults.
        fleet_fired = sum(
            1 for e in journal
            if e.get("kind") in ("fleet.worker_kill", "fleet.worker_stall")
        )
        return InvariantCheck(
            "ledger_reconciles",
            passed=registry_ok and not kills_unaccounted
            and journal_retries == retries_counter
            and finished_total == len(accepted)
            and fleet_fired >= planned_faults,
            details={
                "planned_worker_faults": planned_faults,
                "journaled_worker_faults": fleet_fired,
                "controller_injected": counts,
                "registry_matches_journal": registry_ok,
                "worker_journal_kills": journaled_kills,
                "observed_deaths": deaths_by_worker,
                "kills_without_observed_death": kills_unaccounted,
                "router_retries_total": retries_counter,
                "journal_retries": journal_retries,
                "finished_total": finished_total,
                "accepted": len(accepted),
            },
        )

    def _check_fleet_recovered(
        self,
        finish_reasons: Dict[int, Optional[str]],
        first_id_after_fault: Optional[int],
        final_states: Dict[int, str],
        fault_planned: bool,
    ) -> InvariantCheck:
        """After a replica fault, LATER requests must complete normally (the
        fleet degraded instead of failing) and no replica may end the run
        ejected — the cooldown/rejoin machinery must have brought it back."""
        if not fault_planned:
            return InvariantCheck(
                "fleet_recovered", True, {"note": "no router fault in plan"}
            )
        later = {
            rid: fr for rid, fr in finish_reasons.items()
            if first_id_after_fault is not None and rid >= first_id_after_fault
        }
        bad = {
            rid: fr for rid, fr in later.items()
            if fr not in ("eos", "length", "timeout")
        }
        still_ejected = {i: s for i, s in final_states.items() if s == "ejected"}
        return InvariantCheck(
            "fleet_recovered",
            passed=bool(later) and not bad and not still_ejected,
            details={
                "requests_after_fault": len(later),
                "abnormal_after_fault": bad,
                "final_replica_states": final_states,
                "first_id_after_fault": first_id_after_fault,
            },
        )

    @staticmethod
    def _check_no_route_to_ejected(
        routing_log: List[dict], state_log: List[dict]
    ) -> InvariantCheck:
        """Audit every routing decision against the health history: the router
        journals the replica's state at decision time, and the state log lets
        us independently reconstruct ejected/draining windows."""
        bad = [e for e in routing_log if e.get("state") in ("ejected", "draining", "retired")]
        # Independent reconstruction: walk the state log and verify no routing
        # timestamp lands inside an (ejected -> rejoining) window.
        windows: Dict[int, List[List[float]]] = {}
        for tr in state_log:
            if tr["to"] == "ejected":
                windows.setdefault(tr["replica"], []).append([tr["t"], float("inf")])
            elif tr["from"] == "ejected" and tr["replica"] in windows:
                spans = windows[tr["replica"]]
                if spans and spans[-1][1] == float("inf"):
                    spans[-1][1] = tr["t"]
        inside = [
            e for e in routing_log
            if any(
                lo < e["t"] < hi
                for lo, hi in windows.get(e["replica"], [])
            )
        ]
        return InvariantCheck(
            "no_route_to_ejected",
            passed=not bad and not inside,
            details={
                "decisions": len(routing_log),
                "routed_while_unroutable": bad,
                "routed_inside_ejected_window": inside,
                "ejection_windows": {k: v for k, v in windows.items()},
            },
        )

    def _check_router_ledger(
        self,
        routing_log: List[dict],
        retries_counter: int,
        accepted: List[int],
        finish_reasons: Dict[int, Optional[str]],
    ) -> InvariantCheck:
        counts = self.session.counts()
        registry_ok = all(
            self.session.registry.value("chaos_injected_total", {"kind": kind}) == count
            for kind, count in counts.items()
        )
        journal_retries = sum(1 for e in routing_log if e["kind"] == "retry")
        finished_total = sum(1 for r in finish_reasons.values() if r is not None)
        return InvariantCheck(
            "ledger_reconciles",
            passed=registry_ok and journal_retries == retries_counter
            and finished_total == len(accepted),
            details={
                "injected_counts": counts,
                "registry_matches_journal": registry_ok,
                "router_retries_total": retries_counter,
                "journal_retries": journal_retries,
                "finished_total": finished_total,
                "accepted": len(accepted),
            },
        )

    @staticmethod
    def _check_page_ledger(engine) -> InvariantCheck:
        """An engine must end a drained run with ZERO pages in use — every
        refcount returned through finish/cancel/error/abort, none leaked by the
        blast-radius rebuild — and a structurally consistent pool: no page both
        free and cached, no prefix registration pointing at a freed page (the
        'resurrected prefix' failure a post-recovery stale hash map would
        cause)."""
        pool = engine.pool
        problems = pool.check_consistency()
        return InvariantCheck(
            "page_ledger",
            passed=pool.pages_in_use == 0 and not problems,
            details={
                "pages_in_use_after_drain": pool.pages_in_use,
                "consistency_problems": problems,
                **pool.stats(),
            },
        )

    def _check_engine_recovered(
        self, finish_reasons: Dict[int, Optional[str]], first_id_after_error: Optional[int]
    ) -> InvariantCheck:
        """After a dispatch failure's blast radius, requests submitted LATER
        must still complete normally — the engine degrades per-step, never
        permanently."""
        if first_id_after_error is None:
            return InvariantCheck(
                "engine_recovered", True, {"note": "no dispatch_error fault in plan"}
            )
        later = {r: fr for r, fr in finish_reasons.items() if r >= first_id_after_error}
        bad = {r: fr for r, fr in later.items() if fr == "error"}
        return InvariantCheck(
            "engine_recovered",
            passed=bool(later) and not bad,
            details={
                "requests_after_error": len(later),
                "errored_after_recovery": bad,
                "first_id_after_error": first_id_after_error,
            },
        )

    def _check_serve_ledger(self, engine, accepted: List[int]) -> InvariantCheck:
        counts = self.session.counts()
        registry_ok = all(
            self.session.registry.value("chaos_injected_total", {"kind": kind}) == count
            for kind, count in counts.items()
        )
        finished_total = sum(engine.stats["finish_reasons"].values())
        return InvariantCheck(
            "ledger_reconciles",
            passed=registry_ok and finished_total == len(accepted),
            details={
                "injected_counts": counts,
                "registry_matches_journal": registry_ok,
                "finished_total": finished_total,
                "accepted": len(accepted),
            },
        )

    # ---------------------------------------------------------------- trace checks
    def _trace_records(self) -> List[dict]:
        """Everything THIS run traced: the streamed files when a trace dir is
        armed (they carry every process, including SIGKILLed children whose
        ring died with them), else the in-memory ring. Dir records are
        filtered to this run's trace id — the dir may legitimately hold other
        tracers' spans (a prior run reusing the dir, the workload
        Accelerator's own default tracer armed off ACCELERATE_TPU_TRACE_DIR
        with a different id) and foreign spans must not fail the invariant."""
        if self.trace_dir:
            return [
                r for r in collect_trace_dir(self.trace_dir)
                if r.get("trace_id") == self.tracer.trace_id
            ]
        return self.tracer.recorder.records()

    def _check_trace_complete(
        self, journal: Dict[str, Any], supervised: bool = False
    ) -> InvariantCheck:
        """The stitched timeline must be a complete account of the sweep:
        every journaled injection appears as a `chaos.*` event (reconciling
        with `chaos_injected_total`), a kill that fired left a crash boundary,
        a restart that happened shows up as a post-boundary attempt, every
        span parents into the timeline (no orphans), and the whole sweep
        shares ONE trace id across processes."""
        kill_kinds = {"proc.sigkill", "proc.sigterm", "fs.crash_in_rename"}
        records = self._trace_records()
        if supervised and not self.trace_dir:
            return InvariantCheck(
                "trace_complete", True,
                {"note": "no trace_dir armed; child spans were not durable"},
            )
        details: Dict[str, Any] = {"records": len(records)}
        problems: List[str] = []

        spans = [r for r in records if r.get("kind") in ("span", "span_start")]
        events = [r for r in records if r.get("kind") == "event"]
        known_ids = {r.get("span_id") for r in spans}
        orphans = [
            r.get("name") for r in spans
            if r.get("parent_id") is not None and r.get("parent_id") not in known_ids
        ]
        if orphans:
            problems.append(f"orphan spans (parent id unresolved): {sorted(set(orphans))}")
        # _trace_records already scopes to this run's trace id; the check here
        # is that the run's own processes all STITCHED onto it (a worker that
        # failed to inherit the id would simply be missing from `records`).
        details["trace_id"] = self.tracer.trace_id

        injection_events = [
            e for e in events
            if e["name"].startswith("chaos.") and e["name"] != "chaos.crash_boundary"
        ]
        injected = len(self.session.injections)
        counter_total = sum(
            m.get("value", 0) for m in self.session.registry.snapshot()
            if m["name"] == "chaos_injected_total"
        )
        details["injections_journaled"] = injected
        details["injection_events"] = len(injection_events)
        details["chaos_injected_total"] = counter_total
        if len(injection_events) != injected or counter_total != injected:
            problems.append("injection events do not reconcile with the journal/counters")

        fired_kills = [e for e in self.session.injections if e["kind"] in kill_kinds]
        details["kill_injections"] = len(fired_kills)
        if fired_kills:
            boundaries = [e["t_unix"] for e in events if e["name"] == "chaos.crash_boundary"]
            boundaries += [
                e["t_unix"] for e in events
                if e["name"] == "supervisor.child_exit" and e["attrs"].get("exit_code") != 0
            ]
            details["crash_boundaries"] = len(boundaries)
            if not boundaries:
                problems.append("kill injections fired but no crash boundary was traced")
            elif journal["attempts"] > 1:
                first = min(boundaries)
                resumed = [
                    r for r in spans
                    if r.get("name") == "train.attempt" and r.get("start_unix", 0) > first
                ] + [e for e in events if e["name"] == "train.resume" and e["t_unix"] > first]
                details["post_crash_attempts"] = len(resumed)
                if not resumed:
                    problems.append(
                        "restarts happened but no attempt/resume appears after the "
                        "first crash boundary"
                    )
        details["problems"] = problems
        return InvariantCheck("trace_complete", passed=not problems, details=details)

    def _check_serve_trace(self, accepted: List[int]) -> InvariantCheck:
        """Serving half of trace completeness: every ACCEPTED request left a
        `serve.request` span carrying a terminal finish_reason (submit ->
        finish is fully covered even through blast-radius recoveries), and
        injected serve faults appear as `chaos.serve.*` events."""
        from ..serving import FINISH_REASONS

        records = self._trace_records()
        request_spans = {
            r["attrs"].get("request_id"): r
            for r in records
            if r.get("kind") == "span" and r.get("name") == "serve.request"
        }
        missing = [rid for rid in accepted if rid not in request_spans]
        non_terminal = {
            rid: request_spans[rid]["attrs"].get("finish_reason")
            for rid in accepted
            if rid in request_spans
            and request_spans[rid]["attrs"].get("finish_reason") not in FINISH_REASONS
        }
        injection_events = sum(
            1 for r in records
            if r.get("kind") == "event" and r["name"].startswith("chaos.serve.")
        )
        serve_injected = sum(
            1 for e in self.session.injections if e["kind"].startswith("serve.")
        )
        return InvariantCheck(
            "trace_complete",
            passed=not missing and not non_terminal and injection_events == serve_injected,
            details={
                "accepted": len(accepted),
                "request_spans": len(request_spans),
                "missing_spans": missing,
                "non_terminal_spans": non_terminal,
                "serve_injections": serve_injected,
                "serve_injection_events": injection_events,
            },
        )

    # ---------------------------------------------------------------- shared checks
    @staticmethod
    def _check_resume_exactness(journal: Dict[str, Any]) -> InvariantCheck:
        failures = []
        known = {}
        for entry in journal["intents"]:
            known.setdefault(entry["step"], set()).add(entry["digest"])
        for entry in journal["saves"]:
            known.setdefault(entry["step"], set()).add(entry["digest"])
        for resume in journal["resumes"]:
            step, digest = resume.get("step"), resume.get("digest")
            if step is None:
                failures.append({"resume": resume, "why": "resolved checkpoint has no step"})
            elif step not in known:
                failures.append({"resume": resume, "why": f"no committed save for step {step}"})
            elif digest not in known[step]:
                failures.append({"resume": resume, "why": "restored params != committed digest"})
        return InvariantCheck(
            "resume_exactness",
            passed=not failures,
            details={"resumes": len(journal["resumes"]), "failures": failures},
        )

    @staticmethod
    def _check_no_torn_resolved(journal: Dict[str, Any], checkpoint_base: str) -> InvariantCheck:
        failures = []
        for resume in journal["resumes"]:
            if not resume.get("independently_verified"):
                failures.append({"resume": resume, "why": "resolved checkpoint fails digests"})
            elif resume.get("expected_step") is not None and resume.get("step") != resume.get(
                "expected_step"
            ):
                failures.append({
                    "resume": resume,
                    "why": "resolve() skipped or overshot the newest verified checkpoint",
                })
        # Terminal state: whatever 'latest' would resolve to now must verify.
        final_latest = independent_latest_step(checkpoint_base)
        return InvariantCheck(
            "no_torn_resolved",
            passed=not failures,
            details={
                "resumes": len(journal["resumes"]),
                "failures": failures,
                "final_verified_latest_step": final_latest,
            },
        )

    @staticmethod
    def _check_zero_state_sharded(journal: Dict[str, Any]) -> InvariantCheck:
        """2D-mesh workloads only: every attempt journals its optimizer-state
        layout after prepare (``layout`` records) and after every restore
        (``zero_state_sharded`` on resume records) — ALL of them must report
        the moments live-sharded along "data". A restart that silently
        replicates the state trains the same numbers while spending data_n x
        the HBM, which is exactly the failure mode a byte-layout invariant
        exists to catch."""
        records = [
            {"kind": "layout", **e} for e in journal.get("layouts", [])
        ] + [
            {"kind": "resume", "step": e.get("step"),
             "zero_state_sharded": e.get("zero_state_sharded")}
            for e in journal.get("resumes", [])
        ]
        failures = [r for r in records if r.get("zero_state_sharded") is not True]
        return InvariantCheck(
            "zero_state_sharded",
            passed=bool(records) and not failures,
            details={"records": len(records), "failures": failures},
        )

    @staticmethod
    def _check_restart_budget(
        completed: bool, restarts: int, max_restarts: int, downtime_s: float,
        downtime_budget_s: float,
    ) -> InvariantCheck:
        return InvariantCheck(
            "restart_budget",
            passed=completed and restarts <= max_restarts and downtime_s <= downtime_budget_s,
            details={
                "completed": completed,
                "restarts": restarts,
                "max_restarts": max_restarts,
                "downtime_s": round(downtime_s, 6),
                "downtime_budget_s": downtime_budget_s,
            },
        )

    def _check_ledger_reconciles(
        self, ledger: Dict[str, float], journal: Dict[str, Any], async_save: bool = False
    ) -> InvariantCheck:
        counts = self.session.counts()
        registry_ok = all(
            self.session.registry.value("chaos_injected_total", {"kind": kind}) == count
            for kind, count in counts.items()
        )
        fired = self.session.event_fire_counts()
        injected_fsync_s = sum(
            float(ev.args.get("delay_s", 0.05)) * fired[i]
            for i, ev in enumerate(self.plan.events)
            if ev.kind == "fs.slow_fsync"
        )
        if async_save:
            # Async saves: an injected stall runs on the background committer,
            # so its time must land in checkpoint_async_commit_seconds (folded
            # into the ledger as "checkpoint_async_commit") and/or in the
            # blocking barrier charge when the next save caught the commit in
            # flight — never vanish. An ABORTED commit (killed mid-stall)
            # legitimately truncates its recording, so the sweep-stable
            # assertion is existence, not magnitude: stalls injected => commit
            # and/or blocking time was accounted. The exact only-blocking-time
            # split is pinned by the deterministic goodput property test.
            accounted = ledger.get("checkpoint", 0.0) + ledger.get("checkpoint_async_commit", 0.0)
            checkpoint_ok = injected_fsync_s == 0.0 or accounted > 0.0
        else:
            # Injected fsync stalls happen inside save_state, so the goodput
            # ledger's "checkpoint" cause must carry at least that much (10%
            # scheduling tolerance); every resume charges "restart".
            checkpoint_ok = ledger.get("checkpoint", 0.0) >= 0.9 * injected_fsync_s
        restart_ok = (not journal["resumes"]) or ledger.get("restart", 0.0) > 0.0
        return InvariantCheck(
            "ledger_reconciles",
            passed=registry_ok and checkpoint_ok and restart_ok,
            details={
                "injected_counts": counts,
                "registry_matches_journal": registry_ok,
                "goodput_ledger_s": {k: round(v, 6) for k, v in sorted(ledger.items())},
                "injected_fsync_s": round(injected_fsync_s, 6),
                "async_save": async_save,
            },
        )

    # ---------------------------------------------------------------- report assembly
    def _report(
        self,
        workload: str,
        checks: List[InvariantCheck],
        diagnostics: Optional[List[dict]] = None,
    ) -> InvariantReport:
        return InvariantReport(
            plan=self.plan.to_dict(),
            workload=workload,
            checks=checks,
            injections=list(self.session.injections),
            metrics=self.session.registry.snapshot(),
            diagnostics=list(diagnostics or []),
        )
