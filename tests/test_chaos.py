"""Chaos-subsystem tests: the acceptance sweeps of the fault-injection tentpole.

Pins, on CPU inside tier-1 time:

  1. plan semantics — JSON round trip, the ``ACCELERATE_TPU_FAULT_PLAN`` env
     protocol, trigger evaluation (step / call-count / path / times);
  2. the SIGKILL sweep — a kill at EVERY step boundary of an 8-step supervised
     run resumes exactly from the last committed checkpoint;
  3. the torn-write sweep — post-commit corruption at a range of byte offsets
     of a checkpoint MANIFEST (and the npz payload) never gets a torn
     checkpoint resolved by `resolve("latest")`;
  4. commit-window faults — SIGTERM landing inside the staged-dir commit,
     crashes inside the rename window, transient EIO during publish (the
     retry-idempotency bug this PR fixed);
  5. serving chaos — an injected dispatch stall + queue-full burst drains with
     every request carrying a terminal finish_reason, and the engine keeps
     serving after a dispatch failure;
  6. the CLI contract — `accelerate-tpu chaos run` exits 0 on a clean plan and
     non-zero on the seeded-regression fixture (the harness can tell a broken
     stack from a healthy one);
  7. telemetry reconciliation — `chaos_injected_total{kind=...}` matches the
     injection journal and injected downtime lands in the goodput ledger.
"""

import json
import os
import sys

import numpy as np
import pytest

from accelerate_tpu.chaos import (
    FAULT_PLAN_ENV,
    ChaosRunner,
    ChaosSession,
    FakeClock,
    FaultEvent,
    FaultPlan,
    InvariantReport,
    builtin_plans,
)

pytestmark = pytest.mark.chaos


# ------------------------------------------------------------------ plan + triggers
def test_plan_json_round_trip():
    plan = FaultPlan(
        name="rt", seed=7,
        events=[
            FaultEvent(kind="proc.sigkill", at_step=3),
            FaultEvent(kind="fs.torn_write", path_pattern="MANIFEST.json", at_call=2,
                       args={"offset": 17}, times=2),
        ],
        notes="round trip",
    )
    restored = FaultPlan.from_json(plan.to_json())
    assert restored == plan
    assert restored.events[1].args == {"offset": 17}


def test_plan_rejects_unknown_kind_and_fields():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultEvent(kind="fs.does_not_exist")
    with pytest.raises(ValueError, match="unknown FaultEvent field"):
        FaultEvent.from_dict({"kind": "proc.sigkill", "at_stepp": 3})


def test_plan_env_protocol_inline_and_file(tmp_path):
    plan = FaultPlan(name="envp", events=[FaultEvent(kind="proc.sigterm", at_step=1)])
    # inline JSON
    restored = FaultPlan.from_env({FAULT_PLAN_ENV: plan.to_json(indent=None)})
    assert restored == plan
    # file path
    path = plan.save(str(tmp_path / "plan.json"))
    assert FaultPlan.from_env({FAULT_PLAN_ENV: path}) == plan
    # unset -> no chaos armed
    assert FaultPlan.from_env({}) is None


def test_trigger_semantics_call_step_path_times():
    plan = FaultPlan(events=[
        FaultEvent(kind="fs.io_error", path_pattern="model.npz*", at_call=2),
        FaultEvent(kind="proc.sigkill", at_step=5),
        FaultEvent(kind="fs.slow_fsync", path_pattern="*.bin", times=2),
    ])
    session = ChaosSession(plan, clock=FakeClock())
    # path-triggered events never fire at step sites and vice versa
    assert session.fire("fs.io_error", step=1) == []
    assert session.fire("proc.sigkill", path="/x/model.npz") == []
    # at_call counts MATCHING calls only
    assert session.fire("fs.io_error", path="/ck/model.npz") == []       # matching call 1: no fire
    assert session.fire("fs.io_error", path="/ck/optimizer.npz") == []   # non-matching: not counted
    assert len(session.fire("fs.io_error", path="/ck/model.npz")) == 1   # matching call 2: fires
    assert session.counts().get("fs.io_error", 0) == 1
    # step trigger
    assert session.fire("proc.sigkill", step=4) == []
    assert len(session.fire("proc.sigkill", step=5)) == 1
    assert session.fire("proc.sigkill", step=5) == []  # times=1 exhausted
    # times=2 fires twice, then disarms
    assert len(session.fire("fs.slow_fsync", path="a.bin")) == 1
    assert len(session.fire("fs.slow_fsync", path="b.bin")) == 1
    assert session.fire("fs.slow_fsync", path="c.bin") == []
    # every firing counted in the registry
    assert session.registry.value("chaos_injected_total", {"kind": "fs.slow_fsync"}) == 2


def test_multi_seam_kinds_stay_disjoint():
    """`proc.sigterm` has two seams (step boundary, artifact write). An event
    without a `path_pattern` belongs to the step seam only — the write seam
    (which passes require_pattern) must neither fire it nor advance its call
    counter, so `at_call` counts one seam's calls, never an interleaving."""
    plan = FaultPlan(events=[FaultEvent(kind="proc.sigterm", at_call=2)])
    session = ChaosSession(plan, clock=FakeClock())
    # artifact-write seam: not evaluated at all for a pattern-less event
    assert session.fire("proc.sigterm", path="/ck/model.npz", require_pattern=True) == []
    assert session.fire("proc.sigterm", path="/ck/model.npz", require_pattern=True) == []
    # step seam: the 2nd STEP call fires — write-seam calls did not count
    assert session.fire("proc.sigterm", step=0) == []
    assert len(session.fire("proc.sigterm", step=1)) == 1


def test_after_s_trigger_with_fake_clock():
    clock = FakeClock()
    plan = FaultPlan(events=[FaultEvent(kind="serve.dispatch_stall", after_s=10.0)])
    session = ChaosSession(plan, clock=clock)
    assert session.fire("serve.dispatch_stall") == []
    clock.sleep(11.0)
    assert len(session.fire("serve.dispatch_stall")) == 1


# ------------------------------------------------------------------ train sweeps
def test_sigkill_at_every_boundary_of_8_step_run_resumes_exactly(tmp_path):
    """THE acceptance sweep: one run, a SIGKILL scripted at every one of the 8
    step boundaries — nine attempts, eight resumes, each landing exactly on the
    last committed checkpoint (step + parameter digest)."""
    plan = FaultPlan(
        name="kill-every-boundary",
        events=[FaultEvent(kind="proc.sigkill", at_step=k) for k in range(8)],
    )
    runner = ChaosRunner(plan)
    report = runner.run_train(str(tmp_path), steps=8, max_restarts=16)
    assert report.ok, report.render_text()
    assert len(report.injections) == 8
    by_name = {c.name: c for c in report.checks}
    assert by_name["resume_exactness"].details["resumes"] == 8
    assert by_name["restart_budget"].details["restarts"] == 8
    assert by_name["restart_budget"].details["completed"] is True


@pytest.mark.parametrize(
    "target,args",
    [
        ("MANIFEST.json", {"offset": 0}),
        ("MANIFEST.json", {"offset_frac": 0.5}),
        ("MANIFEST.json", {"offset_frac": 0.9, "flip": True}),
        ("model.npz", {"offset": 1}),
        ("model.npz", {"offset_frac": 0.5, "flip": True}),
    ],
)
def test_torn_write_sweep_never_resolves_torn_checkpoint(tmp_path, target, args):
    """Post-commit corruption at a range of byte offsets — truncation and bit
    flips, on the checkpoint MANIFEST and the model payload. Resume after the
    kill must fall back past the torn newest checkpoint, and the re-save must
    replace it with one that verifies."""
    plan = FaultPlan(
        name="torn-sweep",
        events=[
            FaultEvent(kind="fs.torn_write", path_pattern=target, at_call=2, args=args),
            FaultEvent(kind="proc.sigkill", at_step=1),
        ],
    )
    runner = ChaosRunner(plan)
    report = runner.run_train(str(tmp_path), steps=4)
    assert report.ok, report.render_text()
    by_name = {c.name: c for c in report.checks}
    assert by_name["no_torn_resolved"].details["resumes"] == 1
    # the terminal state re-verified independently: latest committed step is the last one
    assert by_name["no_torn_resolved"].details["final_verified_latest_step"] == 3


def test_sigterm_inside_staged_commit_preempts_gracefully(tmp_path):
    """SIGTERM delivered while an artifact is mid-commit inside the staging dir
    (the expected-bug window): the latch must not tear the commit — the save
    completes, the run preempts gracefully at the boundary, and the resume is
    exact."""
    plan = FaultPlan(
        name="sigterm-mid-commit",
        events=[FaultEvent(kind="proc.sigterm", path_pattern="model.npz*", at_call=3)],
    )
    runner = ChaosRunner(plan)
    report = runner.run_train(str(tmp_path), steps=4)
    assert report.ok, report.render_text()
    assert [e["kind"] for e in report.injections] == ["proc.sigterm"]


def test_crash_in_rename_window_of_staged_manifest(tmp_path):
    """A kill between the payload fsync and the rename of the staged MANIFEST:
    the checkpoint never becomes visible, the retry (next attempt) lands the
    same step cleanly."""
    plan = FaultPlan(
        name="rename-crash",
        events=[FaultEvent(kind="fs.crash_in_rename", path_pattern="MANIFEST.json", at_call=2)],
    )
    report = ChaosRunner(plan).run_train(str(tmp_path), steps=4)
    assert report.ok, report.render_text()


def test_transient_eio_on_latest_pointer_does_not_lose_commit(tmp_path):
    """Regression pin for the publish-retry idempotency fix: a transient EIO on
    the `latest` pointer write lands AFTER the directory rename; the retry used
    to re-run `os.replace` on the vanished staging dir and fail a save whose
    checkpoint was already committed."""
    plan = FaultPlan(
        name="pointer-eio",
        events=[FaultEvent(kind="fs.io_error", path_pattern="latest", at_call=2,
                           args={"errno": "EIO"})],
    )
    report = ChaosRunner(plan).run_train(str(tmp_path), steps=3)
    assert report.ok, report.render_text()
    assert report.injections and report.injections[0]["kind"] == "fs.io_error"


def test_enospc_on_staged_manifest_write_retries(tmp_path):
    plan = FaultPlan(
        name="manifest-enospc",
        events=[FaultEvent(kind="fs.io_error", path_pattern="MANIFEST.json", at_call=1,
                           args={"errno": "ENOSPC"})],
    )
    report = ChaosRunner(plan).run_train(str(tmp_path), steps=3)
    assert report.ok, report.render_text()


def test_chaos_counters_reconcile_with_goodput_ledger(tmp_path):
    """Satellite pin: a chaos run's injected-fault counters reconcile with the
    goodput-ledger entries it produces — the slow-fsync delay shows up in the
    'checkpoint' cause, resumes charge 'restart', and every injection journal
    entry has a matching `chaos_injected_total` count."""
    plan = FaultPlan(
        name="ledger",
        events=[
            FaultEvent(kind="fs.slow_fsync", path_pattern="model.npz*", at_call=1,
                       args={"delay_s": 0.05}),
            FaultEvent(kind="proc.sigkill", at_step=1),
        ],
    )
    runner = ChaosRunner(plan)
    report = runner.run_train(str(tmp_path), steps=3)
    assert report.ok, report.render_text()
    ledger_check = next(c for c in report.checks if c.name == "ledger_reconciles")
    details = ledger_check.details
    assert details["registry_matches_journal"] is True
    assert details["injected_counts"] == {"fs.slow_fsync": 1, "proc.sigkill": 1}
    assert details["goodput_ledger_s"]["checkpoint"] >= 0.045  # the injected stall, -10% tolerance
    assert details["goodput_ledger_s"].get("restart", 0.0) > 0.0  # the resume charged
    # the counters are real registry instruments, visible in the snapshot
    counter_rows = [m for m in report.metrics if m["name"] == "chaos_injected_total"]
    assert {row["labels"]["kind"]: row["value"] for row in counter_rows} == {
        "fs.slow_fsync": 1.0, "proc.sigkill": 1.0,
    }


def test_seeded_regression_fixture_goes_red(tmp_path):
    """The harness must detect a broken stack: with digest verification
    neutered and a torn newest manifest, resolve() hands resume a torn
    checkpoint — the independent invariant checker flags it and the report
    comes back violated."""
    report = ChaosRunner(builtin_plans()["seeded-regression"]).run_train(str(tmp_path), steps=4)
    assert not report.ok
    failed = {c.name for c in report.violated}
    assert "no_torn_resolved" in failed


# ------------------------------------------------------------------ supervised subprocess
def test_supervised_run_with_real_signals_resumes_via_env_protocol(tmp_path):
    """End-to-end: the real `Supervisor` over the real subprocess workload, the
    plan propagated via ACCELERATE_TPU_FAULT_PLAN. A REAL SIGTERM at step 1
    exercises the PreemptionHandler → preemption checkpoint → exit 143 → respawn
    handoff; a REAL SIGKILL at step 3 exercises the crash-restart path. Both
    resumes are exact and the run completes inside the budget."""
    plan = FaultPlan(name="supervised-signals", events=[
        FaultEvent(kind="proc.sigterm", at_step=1),
        FaultEvent(kind="proc.sigkill", at_step=3),
    ])
    runner = ChaosRunner(plan)
    report = runner.run_supervised_train(str(tmp_path), steps=5, max_restarts=3)
    assert report.ok, report.render_text()
    supervisor_check = next(c for c in report.checks if c.name == "supervisor")
    assert supervisor_check.details["restarts"] == 1
    assert supervisor_check.details["preemption_handoffs"] == 1
    # the workload journaled both injections before the faults landed
    assert sorted(e["kind"] for e in report.injections) == ["proc.sigkill", "proc.sigterm"]
    resumes = next(c for c in report.checks if c.name == "resume_exactness").details["resumes"]
    assert resumes == 2


def test_supervised_mesh_2d_keeps_zero_state_sharded_across_restart(tmp_path):
    """The 2D-training chaos sweep: the subprocess workload trains the small
    MLP on the ("data", "model") mesh with sharding_rules="auto" (planner 2D
    plan, ZeRO data-sharded Adam moments), a REAL SIGKILL forces a restart,
    and the `zero_state_sharded` invariant holds across every attempt AND the
    post-restore state — a resume that silently replicated the moments would
    train identically while spending data_n x the optimizer HBM."""
    plan = FaultPlan(name="supervised-2d-kill", events=[
        FaultEvent(kind="proc.sigkill", at_step=1),
    ])
    runner = ChaosRunner(plan)
    report = runner.run_supervised_train(
        str(tmp_path), steps=3, max_restarts=3, mesh_2d=True
    )
    assert report.ok, report.render_text()
    zero_check = next(c for c in report.checks if c.name == "zero_state_sharded")
    assert zero_check.passed, zero_check.details
    # Both the pre-fault attempt and the post-restart attempt journaled their
    # layout, and the resume record itself carries the restored verdict.
    assert zero_check.details["records"] >= 3
    resumes = next(c for c in report.checks if c.name == "resume_exactness").details["resumes"]
    assert resumes == 1


def test_mpmd_injected_kill_resumes_nonuniform_layout_exactly(tmp_path):
    """Chaos on the MPMD pipeline runtime: an `InjectedKill` at a step
    boundary ends the attempt exactly like a SIGKILL ends a process; the
    'respawn' rebuilds the ("data", "model", "pipeline") mesh from scratch,
    reloads the last published checkpoint into the per-stage trees
    (`load_state_dict` re-places every stage on its own submesh), and the
    restored params hash EXACTLY to the killed attempt's last save — with the
    NON-uniform stage layout (`stage_layout_evidence`) identical across the
    restart, and training continuing on the restored state."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs an 8-device mesh (forced CPU devices)")
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.chaos.injectors import InjectedKill, StepBoundaryInjector
    from accelerate_tpu.chaos.runner import params_digest, stage_layout_evidence
    from accelerate_tpu.checkpointing import load_pytree, save_pytree
    from accelerate_tpu.models.llama import LlamaConfig, create_llama_model
    from accelerate_tpu.parallel.sharding import data_spec
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils import ParallelismConfig, set_seed
    from jax.sharding import NamedSharding

    cfg = LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=3,
        num_attention_heads=2, num_key_value_heads=2, max_position_embeddings=32,
        rope_theta=10000.0, tie_word_embeddings=False,
    )

    def spawn():
        AcceleratorState._reset_state()
        GradientState._reset_state()
        PartialState._reset_state()
        set_seed(0)
        bundle = create_llama_model(cfg, seq_len=8)
        bundle.sharding_rules = "auto"
        acc = Accelerator(
            parallelism_config=ParallelismConfig(data=2, model=2, pipeline=2)
        )
        model, _ = acc.prepare(bundle, optax.adam(1e-3))
        return acc, model

    rng = np.random.default_rng(0)
    acc, model = spawn()
    layout = stage_layout_evidence(model)
    assert layout["nonuniform"], layout  # 3 layers / 2 stages: [1, 2] or [2, 1]
    sharding = NamedSharding(acc.mesh, data_spec(acc.mesh))
    batches = [
        jax.device_put({"input_ids": rng.integers(0, 64, (8, 8)).astype(np.int32)}, sharding)
        for _ in range(4)
    ]

    plan = FaultPlan(name="mpmd-kill", events=[FaultEvent(kind="proc.sigkill", at_step=1)])
    boundary = StepBoundaryInjector(ChaosSession(plan), hard=False)
    step_fn = acc.train_step()
    digests = {}
    killed_at = None
    try:
        for step in range(4):
            jax.block_until_ready(step_fn(batches[step]))
            save_pytree(model.state_dict(), str(tmp_path / f"step{step}.npz"))
            digests[step] = params_digest(model)
            boundary.poll(step)
    except InjectedKill:
        killed_at = step
    assert killed_at == 1 and 1 in digests

    # Respawn: fresh state objects, fresh mesh, fresh plan — then restore.
    acc2, model2 = spawn()
    assert stage_layout_evidence(model2) == layout
    model2.load_state_dict(load_pytree(str(tmp_path / f"step{killed_at}.npz")))
    assert params_digest(model2) == digests[killed_at]
    step_fn2 = acc2.train_step()
    loss = float(step_fn2(batches[killed_at + 1]))
    assert np.isfinite(loss)


# ------------------------------------------------------------------ serving chaos
def test_dispatch_stall_and_queue_burst_drain_with_terminal_reasons(tmp_path):
    """The serving acceptance sweep: an injected dispatch stall + a queue-full
    burst against a bounded queue + one dispatch failure — the drain finishes
    with EVERY accepted request carrying a terminal finish_reason, the queue
    never exceeds its cap, and requests submitted after the failure complete
    normally."""
    plan = FaultPlan(
        name="serve-sweep",
        events=[
            FaultEvent(kind="serve.dispatch_stall", at_call=2, args={"delay_s": 0.02}),
            FaultEvent(kind="serve.queue_burst", at_step=1, args={"count": 6}),
            FaultEvent(kind="serve.dispatch_error", at_call=4),
        ],
    )
    runner = ChaosRunner(plan)
    report = runner.run_serve(num_requests=6, max_queue=3)
    assert report.ok, report.render_text()
    by_name = {c.name: c for c in report.checks}
    terminal = by_name["terminal_finish_reasons"].details
    assert terminal["rejected_queue_full"] > 0, "burst never hit the queue bound"
    assert terminal["accepted"] >= 6
    assert by_name["queue_bounded"].details["queue_peak"] <= 3
    assert by_name["engine_recovered"].details.get("requests_after_error", 0) >= 2


def test_consumed_donation_on_chunk_dispatch_recovers():
    """Regression pin WITH TEETH for the donated-cache rebuild: the injected
    chunk failure also deletes the donated cache buffers (what a real
    accelerator dispatch failure does — CPU alone can't model it, donation is
    ignored there). Without the engine's rebuild-on-abort fix, every admission
    after the failure dies on deleted buffers and recovery probes error."""
    plan = FaultPlan(
        name="chunk-consumes-donation",
        events=[FaultEvent(kind="serve.dispatch_error", at_call=2,
                           args={"consume_donated": True})],
    )
    report = ChaosRunner(plan).run_serve(num_requests=4, max_queue=4)
    assert report.ok, report.render_text()
    recovered = next(c for c in report.checks if c.name == "engine_recovered")
    assert recovered.details["requests_after_error"] >= 2


def test_consumed_donation_on_insert_recovers():
    """The insert fn donates (cache, presence) too: an admission dispatch that
    failed AFTER consuming them poisons every slot, so the engine must widen to
    the blast-radius recovery (error in-flight + rebuild) instead of pretending
    the failure was isolated — then keep serving."""
    plan = FaultPlan(
        name="insert-consumes-donation",
        events=[FaultEvent(kind="serve.insert_error", at_call=2,
                           args={"consume_donated": True})],
    )
    report = ChaosRunner(plan).run_serve(num_requests=4, max_queue=4)
    assert report.ok, report.render_text()
    recovered = next(c for c in report.checks if c.name == "engine_recovered")
    assert recovered.details["requests_after_error"] >= 2


def test_insert_error_is_isolated_to_one_request():
    plan = FaultPlan(
        name="insert-error",
        events=[FaultEvent(kind="serve.insert_error", at_call=2)],
    )
    report = ChaosRunner(plan).run_serve(num_requests=4, max_queue=4)
    assert report.ok, report.render_text()
    # exactly one admission errored; everything else completed normally
    finished = next(
        m for m in report.metrics
        if m["name"] == "serving_requests_finished_total" and m["labels"].get("reason") == "error"
    )
    assert finished["value"] == 1.0


def test_consumed_donation_rebuilds_page_pool_without_leaks():
    """The paged-KV extension of the consume_donated sweeps: the run_serve
    workload serves shared-prefix traffic through a PAGED engine, an injected
    chunk failure deletes the donated pool buffers mid-flight (live refcounts,
    live prefix registrations), and recovery must rebuild the page pool AND the
    host ledger — `pages_in_use == 0` after drain, no page both cached and
    free, and no prefix registration resurrecting a page whose content died
    with the rebuild."""
    plan = FaultPlan(
        name="chunk-consumes-donation-paged",
        events=[FaultEvent(kind="serve.dispatch_error", at_call=3,
                           args={"consume_donated": True})],
    )
    report = ChaosRunner(plan).run_serve(num_requests=8, max_queue=6)
    assert report.ok, report.render_text()
    ledger = next(c for c in report.checks if c.name == "page_ledger")
    assert ledger.details["pages_in_use_after_drain"] == 0
    assert ledger.details["consistency_problems"] == []
    # the workload really exercised the paged machinery, not a vacuous pass
    assert ledger.details["pages_total"] > 0


def test_consumed_donation_recovers_with_speculation_enabled():
    """The speculative chunk widens the blast radius's state surface: the
    draft/verify loop carries a per-slot context history and every paged
    admission reserves a draft window. An injected chunk failure that consumes
    the donated cache must rebuild the speculative state too — history
    reseeded per admission, window pages released with the request — and the
    post-recovery probes must complete through the draft/verify executable,
    with the page ledger closing at zero."""
    plan = FaultPlan(
        name="chunk-consumes-donation-speculative",
        events=[FaultEvent(kind="serve.dispatch_error", at_call=3,
                           args={"consume_donated": True})],
    )
    report = ChaosRunner(plan).run_serve(num_requests=8, max_queue=6, speculative=True)
    assert report.ok, report.render_text()
    recovered = next(c for c in report.checks if c.name == "engine_recovered")
    assert recovered.details["requests_after_error"] >= 2
    ledger = next(c for c in report.checks if c.name == "page_ledger")
    assert ledger.details["pages_in_use_after_drain"] == 0
    assert ledger.details["consistency_problems"] == []
    # the sweep drove the speculative executable, not the plain chunk
    steps = next(
        m for m in report.metrics if m["name"] == "serving_spec_verify_steps_total"
    )
    assert steps["value"] > 0


# ------------------------------------------------------- kernel-path serving chaos
@pytest.mark.kernels
def test_smoke_serve_sweep_on_the_kernel_path():
    """The smoke-serve acceptance sweep (stall + queue burst + dispatch
    failure) with `attention_impl="pallas_paged"`: the fused page-walk kernels
    ride inside the one decode executable, so every serving invariant —
    terminal finish_reasons, bounded queue, post-failure recovery — must hold
    unchanged with the kernel on the hot path."""
    plan = builtin_plans()["smoke-serve"]
    report = ChaosRunner(plan).run_serve(
        num_requests=6, max_queue=3, attention_impl="pallas_paged"
    )
    assert report.ok, report.render_text()
    by_name = {c.name: c for c in report.checks}
    assert by_name["terminal_finish_reasons"].details["accepted"] >= 6
    assert by_name["queue_bounded"].details["queue_peak"] <= 3
    assert by_name["engine_recovered"].details.get("requests_after_error", 0) >= 2


@pytest.mark.parametrize("kv_cache_dtype", ["int8", "fp8_e4m3"])
def test_smoke_serve_sweep_on_the_quantized_pool(kv_cache_dtype):
    """The smoke-serve acceptance sweep on a quantized pool (int8, fp8): fault
    paths must exercise the QUANTIZED page pool — dispatch stalls, queue
    bursts, and the blast-radius dispatch failure all land on an engine whose
    pool pages are quantized with per-page-per-head scale pools, and recovery
    must rebuild pools AND scales from zeros with the page ledger still
    closed."""
    plan = builtin_plans()["smoke-serve"]
    report = ChaosRunner(plan).run_serve(
        num_requests=6, max_queue=3, kv_cache_dtype=kv_cache_dtype
    )
    assert report.ok, report.render_text()
    by_name = {c.name: c for c in report.checks}
    assert by_name["terminal_finish_reasons"].details["accepted"] >= 6
    assert by_name["queue_bounded"].details["queue_peak"] <= 3
    assert by_name["engine_recovered"].details.get("requests_after_error", 0) >= 2
    ledger = by_name["page_ledger"]
    assert ledger.details["pages_in_use_after_drain"] == 0
    assert ledger.details["consistency_problems"] == []


@pytest.mark.tp
def test_smoke_serve_sweep_on_a_tensor_parallel_engine():
    """The smoke-serve acceptance sweep with `tp=2`: the engine spans a
    2-device submesh (Megatron-sharded weights, KV pool sharded by KV head),
    and every serving invariant holds unchanged — PLUS the new
    `tp_pool_sharded` check: fault recovery must leave the live pools sharded
    on the submesh, never silently replicated."""
    plan = builtin_plans()["smoke-serve"]
    report = ChaosRunner(plan).run_serve(num_requests=6, max_queue=3, tp=2)
    assert report.ok, report.render_text()
    by_name = {c.name: c for c in report.checks}
    assert by_name["terminal_finish_reasons"].details["accepted"] >= 6
    assert by_name["engine_recovered"].details.get("requests_after_error", 0) >= 2
    sharded = by_name["tp_pool_sharded"]
    assert sharded.details["mesh_devices"] == 2
    assert sharded.details["sharded_leaves"] > 0
    assert sharded.details["unsharded_leaves"] == []


@pytest.mark.tp
def test_consumed_donation_recovers_sharded_on_the_tp_submesh():
    """Blast-radius recovery on a mesh-spanning engine: the injected chunk
    failure deletes the donated SHARDED pool mid-flight; the rebuild must
    recreate the pools (and, int8, the scale pools) from zeros ON THE
    SUBMESH — `tp_pool_sharded` fails on a replicated rebuild — with the
    page ledger closed and post-recovery traffic served by the same warm
    sharded executables."""
    plan = FaultPlan(
        name="chunk-consumes-donation-tp",
        events=[FaultEvent(kind="serve.dispatch_error", at_call=3,
                           args={"consume_donated": True})],
    )
    report = ChaosRunner(plan).run_serve(
        num_requests=8, max_queue=6, tp=2, kv_cache_dtype="int8"
    )
    assert report.ok, report.render_text()
    recovered = next(c for c in report.checks if c.name == "engine_recovered")
    assert recovered.details["requests_after_error"] >= 2
    ledger = next(c for c in report.checks if c.name == "page_ledger")
    assert ledger.details["pages_in_use_after_drain"] == 0
    assert ledger.details["consistency_problems"] == []
    sharded = next(c for c in report.checks if c.name == "tp_pool_sharded")
    assert sharded.passed, sharded.details


@pytest.mark.kernels
def test_consumed_donation_recovers_on_the_quantized_kernel_path():
    """Blast-radius recovery on the quantized KERNEL path: the injected chunk
    failure deletes the donated int8 pool (and its scale pools) mid-flight;
    the rebuild must recreate both from zeros and post-recovery traffic must
    run through the same compiled fused-dequant decode executable — identical
    shapes/dtypes, so the warm executable serves the rebuilt operands."""
    plan = FaultPlan(
        name="chunk-consumes-donation-quantized-kernel",
        events=[FaultEvent(kind="serve.dispatch_error", at_call=3,
                           args={"consume_donated": True})],
    )
    report = ChaosRunner(plan).run_serve(
        num_requests=8, max_queue=6, attention_impl="pallas_paged",
        kv_cache_dtype="int8",
    )
    assert report.ok, report.render_text()
    recovered = next(c for c in report.checks if c.name == "engine_recovered")
    assert recovered.details["requests_after_error"] >= 2
    ledger = next(c for c in report.checks if c.name == "page_ledger")
    assert ledger.details["pages_in_use_after_drain"] == 0
    assert ledger.details["consistency_problems"] == []


@pytest.mark.kernels
def test_consumed_donation_recovers_on_the_kernel_path():
    """Blast-radius recovery rebuilds the KERNEL-path executables identically:
    an injected chunk failure deletes the donated pool buffers mid-flight, the
    engine rebuilds the page pool from zeros, and post-recovery requests must
    complete through the same compiled pallas_paged decode program — page
    ledger closed, no retrace (the rebuilt operands have identical shapes, so
    the warm executable serves them)."""
    plan = FaultPlan(
        name="chunk-consumes-donation-kernel",
        events=[FaultEvent(kind="serve.dispatch_error", at_call=3,
                           args={"consume_donated": True})],
    )
    report = ChaosRunner(plan).run_serve(
        num_requests=8, max_queue=6, attention_impl="pallas_paged"
    )
    assert report.ok, report.render_text()
    recovered = next(c for c in report.checks if c.name == "engine_recovered")
    assert recovered.details["requests_after_error"] >= 2
    ledger = next(c for c in report.checks if c.name == "page_ledger")
    assert ledger.details["pages_in_use_after_drain"] == 0
    assert ledger.details["consistency_problems"] == []
    assert ledger.details["pages_total"] > 0


@pytest.mark.kernels
@pytest.mark.speculative
def test_consumed_donation_recovers_with_speculation_on_the_kernel_path():
    """The speculative sweep with the block-verify KERNEL on the verify seam:
    consumed-donation recovery must rebuild the draft/verify state (history
    reseeded, window pages released) and drive post-recovery traffic through
    the same compiled kernel-path verify executable."""
    plan = FaultPlan(
        name="chunk-consumes-donation-speculative-kernel",
        events=[FaultEvent(kind="serve.dispatch_error", at_call=3,
                           args={"consume_donated": True})],
    )
    report = ChaosRunner(plan).run_serve(
        num_requests=8, max_queue=6, speculative=True, attention_impl="pallas_paged"
    )
    assert report.ok, report.render_text()
    recovered = next(c for c in report.checks if c.name == "engine_recovered")
    assert recovered.details["requests_after_error"] >= 2
    ledger = next(c for c in report.checks if c.name == "page_ledger")
    assert ledger.details["pages_in_use_after_drain"] == 0
    assert ledger.details["consistency_problems"] == []
    steps = next(
        m for m in report.metrics if m["name"] == "serving_spec_verify_steps_total"
    )
    assert steps["value"] > 0


def test_insert_failure_releases_reserved_pages():
    """An isolated insert failure (no donation consumed) must return the pages
    it reserved for the doomed request — a leak here exhausts the pool after
    enough transient admission errors, a failure mode the dense layout never
    had."""
    plan = FaultPlan(
        name="insert-error-paged-ledger",
        events=[FaultEvent(kind="serve.insert_error", at_call=2)],
    )
    report = ChaosRunner(plan).run_serve(num_requests=8, max_queue=6)
    assert report.ok, report.render_text()
    ledger = next(c for c in report.checks if c.name == "page_ledger")
    assert ledger.details["pages_in_use_after_drain"] == 0
    assert ledger.details["consistency_problems"] == []


# ------------------------------------------------------------------ CLI contract
def _run_cli(capsys, *argv):
    from accelerate_tpu.commands.accelerate_cli import get_command_parser

    parser = get_command_parser()
    args = parser.parse_args(list(argv))
    with pytest.raises(SystemExit) as excinfo:
        args.func(args)
    out = capsys.readouterr().out
    return excinfo.value.code, out


def test_cli_list_faults(capsys):
    code, out = _run_cli(capsys, "chaos", "list-faults")
    assert code == 0
    for kind in ("fs.torn_write", "proc.sigkill", "serve.queue_burst"):
        assert kind in out


def test_cli_run_clean_plan_exits_0_and_report_round_trips(capsys, tmp_path):
    report_path = str(tmp_path / "report.json")
    code, out = _run_cli(
        capsys, "chaos", "run", "--plan", "smoke-train", "--steps", "4",
        "--base-dir", str(tmp_path / "run"), "--json", "--report-out", report_path,
    )
    assert code == 0, out
    emitted = json.loads(out)
    assert emitted["ok"] is True and emitted["workload"] == "train"
    # a stored report re-renders with the same verdict/exit code
    loaded = InvariantReport.load(report_path)
    assert loaded.ok and loaded.to_dict()["checks"] == emitted["checks"]
    code2, _ = _run_cli(capsys, "chaos", "report", report_path)
    assert code2 == 0


def test_cli_run_seeded_regression_exits_nonzero(capsys, tmp_path):
    code, out = _run_cli(
        capsys, "chaos", "run", "--plan", "seeded-regression", "--steps", "4",
        "--base-dir", str(tmp_path / "run"),
    )
    assert code == 1
    assert "INVARIANTS VIOLATED" in out
    assert "no_torn_resolved" in out


def test_cli_bad_plan_exits_2(capsys, tmp_path):
    code, _ = _run_cli(capsys, "chaos", "run", "--plan", str(tmp_path / "missing.json"))
    assert code == 2
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"events": [{"kind": "nope"}]}))
    code, _ = _run_cli(capsys, "chaos", "run", "--plan", str(bad))
    assert code == 2


def test_launch_exports_fault_plan_env(tmp_path):
    """`accelerate-tpu launch --fault_plan` joins the env protocol exactly like
    --profile_dir does."""
    import argparse

    from accelerate_tpu.commands.launch import add_launch_args, build_launch_env

    parser = argparse.ArgumentParser()
    add_launch_args(parser)
    plan_file = str(tmp_path / "plan.json")
    args = parser.parse_args(["--fault_plan", plan_file, "script.py"])
    env = build_launch_env(args, {})
    assert env[FAULT_PLAN_ENV] == plan_file


# ------------------------------------------------------------------ async-commit sweeps
def test_async_sigkill_at_every_boundary_resumes_exactly(tmp_path):
    """The async analogue of THE acceptance sweep: SIGKILL at every step
    boundary of an 8-step run whose every save runs through the background
    committer. A kill with a commit in flight aborts it (a dead process cannot
    publish); every resume still lands exactly on the last PUBLISHED
    checkpoint, and no torn checkpoint ever resolves."""
    plan = FaultPlan(
        name="async-kill-every-boundary",
        workload="async-train",
        events=[FaultEvent(kind="proc.sigkill", at_step=k) for k in range(8)],
    )
    runner = ChaosRunner(plan)
    report = runner.run_train(str(tmp_path), steps=8, max_restarts=16, async_save=True)
    assert report.ok, report.render_text()
    assert report.workload == "async-train"
    by_name = {c.name: c for c in report.checks}
    # 8 kills -> 8 restarts. The step-0 commit legitimately races its abort
    # (the kill lands the instant the save is accepted): when it aborted,
    # attempt 2 has nothing to resume FROM — 7 resumes; when it published in
    # time — 8. Every resume that happened must be exact either way.
    assert by_name["resume_exactness"].details["resumes"] in (7, 8)
    assert by_name["restart_budget"].details["restarts"] == 8
    assert by_name["restart_budget"].details["completed"] is True


def test_async_kill_with_commit_in_flight_never_corrupts_previous(tmp_path):
    """ISSUE acceptance boundary 'commit in flight': a slowed background commit
    is provably still running when the step-boundary SIGKILL lands. The abort
    keeps it from publishing; the previously published checkpoint must be the
    verified latest the next attempt resumes from."""
    plan = FaultPlan(
        name="async-kill-in-flight",
        workload="async-train",
        events=[
            # Stall step-1's commit (model.npz write #2) for longer than the
            # boundary takes to kill; the commit is mid-fsync when the run dies.
            FaultEvent(kind="fs.slow_fsync", path_pattern="model.npz", at_call=2,
                       args={"delay_s": 0.3}),
            FaultEvent(kind="proc.sigkill", at_step=1),
        ],
    )
    report = ChaosRunner(plan).run_train(str(tmp_path), steps=4, async_save=True)
    assert report.ok, report.render_text()
    by_name = {c.name: c for c in report.checks}
    # resumed exactly once, from a checkpoint that independently verifies
    assert by_name["resume_exactness"].details["resumes"] == 1
    assert by_name["no_torn_resolved"].details["final_verified_latest_step"] == 3


def test_async_committer_killed_in_rename_window_surfaces_and_recovers(tmp_path):
    """Boundary 'commit mid-write': the committer dies inside an artifact's
    rename window (InjectedKill on the committer thread). The death surfaces at
    the next step boundary like a process kill, the unpublished commit leaves
    only staging litter, and the restart chain completes."""
    plan = FaultPlan(
        name="async-rename-crash",
        workload="async-train",
        events=[FaultEvent(kind="fs.crash_in_rename", path_pattern="optimizer.npz*", at_call=3)],
    )
    report = ChaosRunner(plan).run_train(str(tmp_path), steps=4, async_save=True)
    assert report.ok, report.render_text()
    assert [e["kind"] for e in report.injections] == ["fs.crash_in_rename"]


def test_async_kill_in_publish_rename_window(tmp_path):
    """Boundary 'publish mid-rename': the committer dies between the staged
    manifest write and the directory rename — the checkpoint is fully on disk
    in staging but must never become visible; the previous one stays latest."""
    plan = FaultPlan(
        name="async-publish-crash",
        workload="async-train",
        events=[FaultEvent(kind="fs.crash_in_rename", path_pattern="checkpoint_2", at_call=1)],
    )
    report = ChaosRunner(plan).run_train(str(tmp_path), steps=4, async_save=True)
    assert report.ok, report.render_text()


def test_async_post_publish_torn_write_falls_back(tmp_path):
    """Boundary 'post-publish': corruption lands AFTER an async commit
    published. resolve() must fall back past the torn newest checkpoint on the
    next resume, async exactly like sync."""
    plan = FaultPlan(
        name="async-torn",
        workload="async-train",
        events=[
            FaultEvent(kind="fs.torn_write", path_pattern="model.npz", at_call=2,
                       args={"offset": 1}),
            FaultEvent(kind="proc.sigkill", at_step=1),
        ],
    )
    report = ChaosRunner(plan).run_train(str(tmp_path), steps=4, async_save=True)
    assert report.ok, report.render_text()
    by_name = {c.name: c for c in report.checks}
    assert by_name["no_torn_resolved"].details["resumes"] == 1


def test_async_eio_exhaustion_is_a_commit_failure_crash(tmp_path):
    """Boundary 'commit I/O failure': every write of one step's model artifact
    raises EIO, exhausting the manager's retries inside the background commit.
    The failure surfaces as CheckpointCommitError on the next save's barrier —
    counted as a crash, restarted, run completes."""
    plan = FaultPlan(
        name="async-eio",
        workload="async-train",
        # times=4 with no at_call: the first model.npz write AND its 3 retries
        # all fail — the manager's retry budget is exhausted inside the commit.
        events=[FaultEvent(kind="fs.io_error", path_pattern="model.npz", times=4,
                           args={"errno": "EIO"})],
    )
    report = ChaosRunner(plan).run_train(str(tmp_path), steps=4, async_save=True)
    assert report.ok, report.render_text()
    assert all(e["kind"] == "fs.io_error" for e in report.injections)
    assert len(report.injections) == 4  # initial try + 3 retries, all scripted


def test_smoke_async_ckpt_builtin_plan_is_green(tmp_path):
    """The shipped async-checkpoint chaos fixture holds every invariant."""
    plan = builtin_plans()["smoke-async-ckpt"]
    assert plan.workload == "async-train"
    report = ChaosRunner(plan).run_train(str(tmp_path), steps=6, async_save=True)
    assert report.ok, report.render_text()


def test_supervised_async_preemption_flushes_commits(tmp_path):
    """End-to-end with real signals: the subprocess workload saves through the
    background committer, a REAL SIGTERM lands mid-run, and check_preemption's
    flush + synchronous preemption save hand off cleanly (exit 143, exact
    resume, completion)."""
    plan = FaultPlan(name="supervised-async-term", events=[
        FaultEvent(kind="fs.slow_fsync", path_pattern="model.npz", at_call=2,
                   args={"delay_s": 0.2}),
        FaultEvent(kind="proc.sigterm", at_step=1),
    ])
    runner = ChaosRunner(plan)
    report = runner.run_supervised_train(str(tmp_path), steps=4, async_save=True)
    assert report.ok, report.render_text()
    supervisor_check = next(c for c in report.checks if c.name == "supervisor")
    assert supervisor_check.details["preemption_handoffs"] == 1


def test_cli_run_smoke_async_ckpt_uses_plan_workload(capsys, tmp_path):
    """`chaos run --plan smoke-async-ckpt` picks the plan's own workload
    (async-train) without an explicit --workload flag and exits 0."""
    code, out = _run_cli(
        capsys, "chaos", "run", "--plan", "smoke-async-ckpt", "--steps", "5",
        "--base-dir", str(tmp_path / "run"), "--json",
    )
    assert code == 0, out
    emitted = json.loads(out)
    assert emitted["ok"] is True
    assert emitted["workload"] == "async-train"
    assert emitted["plan"]["workload"] == "async-train"


def test_cli_list_faults_lists_builtin_plans(capsys):
    code, out = _run_cli(capsys, "chaos", "list-faults")
    assert code == 0
    for name in ("smoke-train", "smoke-serve", "smoke-async-ckpt", "seeded-regression"):
        assert name in out
    assert "workload=async-train" in out


# ------------------------------------------------------------------ router sweeps
@pytest.mark.router
def test_smoke_router_builtin_plan_is_green():
    """The acceptance sweep: N=3 replicas under live traffic with a stall, a
    poisoned dispatch AND a kill of distinct replicas — every request reaches
    a terminal finish_reason, no token stream duplicates, the fleet recovers,
    and the router never routed to an ejected replica."""
    plan = builtin_plans()["smoke-router"]
    report = ChaosRunner(plan).run_router(num_requests=10, replicas=3)
    assert report.ok, report.render_text()
    kinds = {e["kind"] for e in report.injections}
    assert {"router.replica_kill", "router.replica_stall", "router.replica_poison"} <= kinds
    names = {c.name for c in report.checks}
    assert {"terminal_finish_reasons", "no_duplicate_streams", "fleet_recovered",
            "no_route_to_ejected", "ledger_reconciles"} <= names


@pytest.mark.router
def test_router_kill_mid_traffic_redispatch_and_recovery():
    """A lone kill of the busiest replica mid-traffic: re-dispatch/replica_lost
    semantics hold and the killed replica is back by drain."""
    plan = FaultPlan(
        name="kill-only", seed=3,
        events=[
            FaultEvent(kind="serve.queue_burst", at_step=1, args={"count": 6}),
            FaultEvent(kind="router.replica_kill", path_pattern="replica_0", at_call=3),
        ],
    )
    report = ChaosRunner(plan).run_router(num_requests=8, replicas=3)
    assert report.ok, report.render_text()
    assert any(e["kind"] == "router.replica_kill" for e in report.injections)


@pytest.mark.router
def test_router_hedging_under_stall():
    """A stalled replica with hedging armed: the hedge copy wins without
    duplicating a stream (the no_duplicate_streams invariant is the pin)."""
    plan = FaultPlan(
        name="stall-hedge", seed=5,
        events=[
            FaultEvent(kind="serve.queue_burst", at_step=1, args={"count": 8}),
            FaultEvent(kind="router.replica_stall", path_pattern="replica_1", at_call=1,
                       args={"delay_s": 0.05}, times=3),
        ],
    )
    report = ChaosRunner(plan).run_router(
        num_requests=8, replicas=2, hedge_after_s=0.0
    )
    assert report.ok, report.render_text()


@pytest.mark.router
def test_cli_run_router_workload(capsys, tmp_path):
    from accelerate_tpu.commands.accelerate_cli import get_command_parser

    report_path = tmp_path / "router_report.json"
    parser = get_command_parser()
    args = parser.parse_args([
        "chaos", "run", "--plan", "smoke-router", "--requests", "8",
        "--replicas", "3", "--json", "--report-out", str(report_path),
    ])
    with pytest.raises(SystemExit) as exit_info:
        args.func(args)
    assert exit_info.value.code == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["workload"] == "router" and payload["ok"]
    assert InvariantReport.load(str(report_path)).ok


# ------------------------------------------------------------------ fleet sweeps
@pytest.mark.fleet
def test_fleet_real_sigkill_mid_traffic_sweep(tmp_path):
    """THE out-of-process acceptance sweep: a real worker PROCESS takes a real
    SIGKILL mid-traffic (worker-side, via the env-propagated plan). Every
    request reaches a terminal reason, no stream duplicates, the respawned
    worker rejoins WARM and serves post-fault traffic, the autoscaler
    converges back to its floor after the burst, and the worker-side journal
    reconciles against the observed process death."""
    plan = FaultPlan(
        name="fleet-kill", seed=1, workload="fleet",
        events=[
            FaultEvent(kind="serve.queue_burst", at_step=1, args={"count": 6}),
            FaultEvent(kind="fleet.worker_kill", path_pattern="worker_0", at_call=3),
        ],
    )
    report = ChaosRunner(plan).run_fleet(
        num_requests=8, replicas=2, workdir=str(tmp_path)
    )
    assert report.ok, report.render_text()
    names = {c.name for c in report.checks}
    assert {"terminal_finish_reasons", "no_duplicate_streams", "fleet_recovered",
            "no_route_to_ejected", "worker_restart_rejoins_warm",
            "ledger_reconciles", "autoscaler_converges"} <= names
    restart = next(c for c in report.checks if c.name == "worker_restart_rejoins_warm")
    assert restart.details["observed_deaths"] >= 1
    ledger = next(c for c in report.checks if c.name == "ledger_reconciles")
    assert ledger.details["worker_journal_kills"] == {"worker_0": 1}
    # The journal entry was durably written BEFORE the SIGKILL landed.
    journal = [json.loads(l) for l in open(tmp_path / "fleet_chaos_journal.jsonl")]
    assert any(e["kind"] == "fleet.worker_kill" and e["worker"] == "worker_0"
               for e in journal)


@pytest.mark.fleet
def test_fleet_worker_stall_surfaces_as_heartbeat_death(tmp_path):
    """A worker stalled past the controller's step timeout is
    indistinguishable from a dead one: the client kills it, the router ejects
    and respawns it warm, and the invariants hold — hang detection by
    TIMEOUT, not cooperation."""
    plan = FaultPlan(
        name="fleet-stall", seed=2, workload="fleet",
        events=[
            # The burst spreads load across the fleet: least-loaded routing
            # with drip-fed traffic would otherwise keep worker_1 idle and the
            # stall trigger (counting ITS OWN step ops) would never arm.
            FaultEvent(kind="serve.queue_burst", at_step=1, args={"count": 6}),
            FaultEvent(kind="fleet.worker_stall", path_pattern="worker_1", at_call=2,
                       args={"delay_s": 30.0}),
        ],
    )
    report = ChaosRunner(plan).run_fleet(
        num_requests=6, replicas=2, autoscale=False, step_timeout_s=3.0,
        workdir=str(tmp_path),
    )
    assert report.ok, report.render_text()
    assert "autoscaler_converges" not in {c.name for c in report.checks}
    ledger = next(c for c in report.checks if c.name == "ledger_reconciles")
    assert ledger.details["observed_deaths"].get("worker_1", 0) >= 1


@pytest.mark.fleet
def test_smoke_fleet_plan_and_workload_inference():
    """The builtin plan round-trips, the CLI infers the fleet workload from
    fleet.* kinds, and the catalog documents the new fault kinds."""
    from accelerate_tpu.chaos.injectors import catalog
    from accelerate_tpu.commands.chaos import _infer_workload

    plan = builtin_plans()["smoke-fleet"]
    assert plan.workload == "fleet"
    assert FaultPlan.from_json(plan.to_json()).to_dict() == plan.to_dict()
    bare = FaultPlan(name="x", events=[
        FaultEvent(kind="fleet.worker_kill", path_pattern="worker_0", at_call=1),
    ])
    assert _infer_workload(bare) == "fleet"
    assert {"fleet.worker_kill", "fleet.worker_stall"} <= set(catalog())


@pytest.mark.fleet
def test_fleet_partition_sweep_over_socket_transport(tmp_path):
    """THE network-chaos acceptance sweep (socket transport): a healable
    partition, injected latency past the frame deadline, and two link flaps —
    every stream stays exactly-once across the reconnects, the controller's
    reconnect counters reconcile against the workers' re-registration
    journal, and a HEALED partition never increments a respawn counter."""
    plan = builtin_plans()["partition-fleet"]
    report = ChaosRunner(plan).run_fleet(
        num_requests=8, replicas=2, transport="socket", workdir=str(tmp_path)
    )
    assert report.ok, report.render_text()
    names = {c.name for c in report.checks}
    assert {"terminal_finish_reasons", "no_duplicate_streams", "fleet_recovered",
            "reconnect_reconciles", "partition_is_not_death"} <= names
    reconciles = next(c for c in report.checks if c.name == "reconnect_reconciles")
    assert reconciles.details["controller_reconnects"] >= 1
    assert (reconciles.details["journaled_reregisters"]
            >= reconciles.details["controller_reconnects"])
    not_death = next(c for c in report.checks if c.name == "partition_is_not_death")
    assert not_death.details["net_attributed_deaths"] == 0
    assert not_death.details["escalation_expected"] is False
    # Workers journaled each accepted re-registration (epoch > 1) durably.
    journal = [json.loads(l) for l in open(tmp_path / "fleet_chaos_journal.jsonl")]
    reregisters = [e for e in journal if e["kind"] == "net.reregister"]
    assert reregisters and all(e["epoch"] >= 2 for e in reregisters)


@pytest.mark.fleet
def test_fleet_partition_past_budget_escalates_to_warm_respawn(tmp_path):
    """A partition window LONGER than `reconnect_deadline_s` must exhaust the
    reconnect budget and escalate through the ordinary death path: the worker
    is respawned warm and rejoins — and the invariants expect that death
    instead of forbidding it."""
    plan = FaultPlan(
        name="partition-escalates", seed=0, workload="fleet",
        events=[FaultEvent(kind="net.partition", path_pattern="worker_0",
                           at_call=4, args={"window_s": 30.0})],
    )
    report = ChaosRunner(plan).run_fleet(
        num_requests=6, replicas=2, transport="socket",
        reconnect_deadline_s=0.6, autoscale=False, workdir=str(tmp_path),
    )
    assert report.ok, report.render_text()
    not_death = next(c for c in report.checks if c.name == "partition_is_not_death")
    assert not_death.details["escalation_expected"] is True
    assert not_death.details["net_attributed_deaths"] >= 1


def test_net_faults_require_socket_transport():
    """net.* kinds damage the socket seam: the fleet workload must reject
    them on the pipe transport up front (no silently-vacuous sweep), and the
    CLI infers workload/transport from them."""
    from accelerate_tpu.chaos.injectors import catalog
    from accelerate_tpu.commands.chaos import _infer_workload

    plan = builtin_plans()["partition-fleet"]
    with pytest.raises(ValueError, match="transport='socket'"):
        ChaosRunner(plan).run_fleet(num_requests=2, replicas=2, transport="pipe")
    assert _infer_workload(FaultPlan(name="x", events=[
        FaultEvent(kind="net.partition", path_pattern="worker_0", at_call=1),
    ])) == "fleet"
    assert {"net.partition", "net.slow", "net.flap"} <= set(catalog())


def test_session_preconsume_blocks_refire_but_not_other_events():
    """`ChaosSession.preconsume` (the worker-restart livelock guard at the
    session layer): consumed firings count against `times`, at_call counters
    advance to the trigger, and path-mismatched or other-kind events are
    untouched."""
    plan = FaultPlan(name="p", events=[
        FaultEvent(kind="fleet.worker_kill", path_pattern="worker_0", at_call=2),
        FaultEvent(kind="fleet.worker_stall", path_pattern="worker_1", at_call=1),
    ])
    session = ChaosSession(plan)
    session.preconsume("fleet.worker_kill", 1, path="worker_0")
    for _ in range(4):
        assert session.fire("fleet.worker_kill", path="worker_0") == []
    # the OTHER worker's stall still fires normally
    assert len(session.fire("fleet.worker_stall", path="worker_1")) == 1
    # a preconsume that matches nothing is a no-op, not an error
    session.preconsume("fleet.worker_kill", 3, path="worker_9")
    # An event with firings LEFT (times=2, one consumed) must keep counting
    # fresh calls: the restarted process's at_call trigger still arms for the
    # remaining firing instead of being disarmed forever.
    plan2 = FaultPlan(name="p2", events=[
        FaultEvent(kind="fleet.worker_kill", path_pattern="worker_0", at_call=2, times=2),
    ])
    session2 = ChaosSession(plan2)
    session2.preconsume("fleet.worker_kill", 1, path="worker_0")
    assert session2.fire("fleet.worker_kill", path="worker_0") == []  # call 1
    assert len(session2.fire("fleet.worker_kill", path="worker_0")) == 1  # call 2: 2nd firing
    assert session2.fire("fleet.worker_kill", path="worker_0") == []  # budget exhausted


# ------------------------------------------------------------------ crash-loop livelock
def test_async_at_step_kill_livelock_surfaces_crash_loop(tmp_path):
    """The PR-9 livelock regression (at_step SIGKILL + async saves, re-armed
    every attempt): the same step is killed before its commit can ever
    publish. The runner must detect the no-forward-progress loop, stop early,
    and tag a `crash_loop` diagnostic — not grind the whole restart budget."""
    plan = FaultPlan(
        name="livelock",
        events=[FaultEvent(kind="proc.sigkill", at_step=1, times=0)],
    )
    report = ChaosRunner(plan).run_train(
        str(tmp_path), steps=4, async_save=True, max_restarts=16
    )
    diags = [d for d in report.diagnostics if d.get("tag") == "crash_loop"]
    assert diags, report.render_text()
    assert diags[0]["why"] == "no_forward_progress"
    budget = next(c for c in report.checks if c.name == "restart_budget")
    assert budget.details["restarts"] < 16, "detector must stop the sweep early"
    assert not report.ok  # a livelocked plan is honestly red
    # round trip: the diagnostic survives save/load
    path = str(tmp_path / "report.json")
    report.save(path)
    assert InvariantReport.load(path).diagnostics == report.diagnostics


def test_single_kill_sweep_does_not_false_positive_crash_loop(tmp_path):
    """A legitimate recovery chain (one kill, checkpoint published, resume
    makes progress) must NOT trip the detector."""
    plan = FaultPlan(name="one-kill", events=[FaultEvent(kind="proc.sigkill", at_step=1)])
    report = ChaosRunner(plan).run_train(str(tmp_path), steps=4, async_save=True)
    assert report.ok, report.render_text()
    assert not report.diagnostics
