"""Training-parallelism benchmark: 1D-replicated vs 2D-ZeRO A/B, and
(``--pipeline-ab``) 2D-ZeRO vs 3D-MPMD-pipeline A/B.

Two passes over the same tiny causal-LM training workload on the one global
mesh (the forced 8-device CPU mesh on the test tier, a real slice on TPU
hardware):

  - **1d**: ``ParallelismConfig(data=-1)`` — pure data parallelism; params,
    grads and optimizer state fully replicated per chip (the pre-planner
    training layout).
  - **2d**: ``ParallelismConfig(data=-1, model=2)`` with
    ``sharding_rules="auto"`` — the cost-model planner's 2D plan: params
    tensor-parallel over "model", optimizer moments ZeRO-sharded along "data"
    (`parallel/planner.plan_train_sharding`).
  - **3d** (``--pipeline-ab`` swaps the pair to 2d-vs-3d): ``ParallelismConfig(
    data=-1, model=TP, pipeline=PP)`` — the 3D MPMD plan: the planner splits
    the layer stack into byte-balanced stages, each stage jit-compiles against
    its own submesh, and the 1F1B schedule runs them (`parallel/mpmd.py`).
    The pass additionally reports per-chip param/opt bytes off the LIVE stage
    shardings vs the plan's prediction, the compiled-once program audit, and
    the pipeline-bubble account: `measure_stage_times` times each stage's
    compiled fwd+bwd per microbatch and `pipeline_bubble_terms` turns that
    into a MEASURED bubble fraction next to the planner's predicted one.

Per pass: steady-state step time under a TraceGuard (0 recompiles / 0 host
transfers after warmup, ASSERTED), per-chip param/grad/optimizer bytes off the
LIVE shardings (`tree_device_nbytes`), and for the 2d pass the planner's
predicted-vs-live per-chip bytes error for all three trees. Loss-trajectory
parity vs the 1d pass is asserted (same data, same init, same optimizer — the
layout must not change the math).

Emits exactly ONE JSON line on stdout (the bench-driver contract); headline is
the 2d per-chip optimizer-state bytes, ``vs_baseline`` the 1d/2d opt-bytes
ratio (how many times less optimizer HBM each chip holds under ZeRO).

`python bench.py --mode train --zero-ab` routes here. It runs on the backend
JAX finds and never switches platform itself: for the CPU test mesh the caller
sets `JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8`.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def log(msg):
    print(f"[train-bench] {msg}", file=sys.stderr, flush=True)


def _build_batches(cfg, global_batch, seq_len, count):
    import numpy as np

    rng = np.random.default_rng(0)
    return [
        {"input_ids": rng.integers(0, cfg.vocab_size, (global_batch, seq_len)).astype(np.int32)}
        for _ in range(count)
    ]


def run_pass(mode, args):
    """One measured pass. Returns (result dict, loss list)."""
    import numpy as np
    import optax

    import jax
    from accelerate_tpu import Accelerator
    from accelerate_tpu.analysis import TraceGuard
    from accelerate_tpu.models import CREATE_BY_FAMILY, get_model_family
    from accelerate_tpu.parallel.sharding import tree_device_nbytes
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils import ParallelismConfig, set_seed

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    set_seed(0)

    family, cfg = get_model_family(args.model)
    bundle = CREATE_BY_FAMILY[family](cfg, seq_len=args.seq_len)
    if mode == "3d":
        bundle.sharding_rules = "auto"
        pcfg = ParallelismConfig(data=-1, model=args.tp, pipeline=args.pp)
    elif mode == "2d":
        bundle.sharding_rules = "auto"
        pcfg = ParallelismConfig(data=-1, model=args.tp)
    else:
        pcfg = ParallelismConfig(data=-1)
    accelerator = Accelerator(parallelism_config=pcfg)
    mesh_axes = {k: v for k, v in dict(accelerator.mesh.shape).items() if v > 1}
    model, opt = accelerator.prepare(bundle, optax.adam(1e-3))

    # Pre-place batches on the mesh (what the prepared DataLoader does): the
    # TraceGuard below forbids host transfers in the steady-state window, and
    # the steady-state input path IS device-resident.
    from jax.sharding import NamedSharding
    from accelerate_tpu.parallel.sharding import data_spec

    batch_sharding = NamedSharding(accelerator.mesh, data_spec(accelerator.mesh))
    batches = [
        jax.device_put(b, jax.tree_util.tree_map(lambda _: batch_sharding, b))
        for b in _build_batches(cfg, args.global_batch, args.seq_len, args.warmup + args.steps)
    ]
    step_fn = accelerator.train_step()
    for batch in batches[: args.warmup]:
        jax.block_until_ready(step_fn(batch))

    guard = TraceGuard(name=f"train-{mode}", on_violation="record")
    raw_losses = []
    t0 = time.perf_counter()
    with guard:
        for batch in batches[args.warmup :]:
            raw_losses.append(step_fn(batch))
        jax.block_until_ready(raw_losses[-1])
    wall = time.perf_counter() - t0
    losses = [float(l) for l in raw_losses]

    assert guard.total_recompiles == 0, (
        f"{mode} pass recompiled in steady state: {guard.report().summary()}"
    )
    assert guard.host_transfers == 0, (
        f"{mode} pass transferred to host in steady state: {guard.transfer_violations}"
    )

    if mode == "3d":
        # MPMD pass: bytes off the LIVE per-stage shardings (busiest stage),
        # the compiled-once audit, and the measured-vs-predicted bubble.
        from accelerate_tpu.parallel.planner import pipeline_bubble_terms

        plan = model.plan
        counts = model.compiled_program_counts()
        multi = {name: n for name, n in counts.items() if n != 1}
        assert not multi, f"3d pass compiled a stage program more than once: {multi}"

        live = model.live_per_chip_bytes()
        stage_times = model.measure_stage_times(batches[0])
        measured_wall, measured_bubble = pipeline_bubble_terms(
            stage_times, plan.num_microbatches, 0.0
        )
        result = {
            "mesh": mesh_axes,
            "steps": args.steps,
            "step_time_s_mean": wall / args.steps,
            "per_chip_param_bytes": live["per_chip_param_bytes"],
            "per_chip_opt_bytes": live["per_chip_opt_bytes"],
            "recompiles": guard.total_recompiles,
            "host_transfers": guard.host_transfers,
            "final_loss": losses[-1],
            "pipeline": {
                "num_stages": plan.num_stages,
                "stage_layers": [
                    len(plan.stage_plan.stage_layers(k)) for k in range(plan.num_stages)
                ],
                "num_microbatches": plan.num_microbatches,
                "stage_times_s": stage_times,
                "measured_wall_s": measured_wall,
                "measured_bubble_fraction": measured_bubble,
                "predicted_bubble_fraction": plan.bubble_fraction,
                "predicted_p2p_time_s": plan.p2p_time_s,
            },
        }
        for tree, predicted, live_key in (
            ("params", plan.cost.per_chip_param_bytes, "per_chip_param_bytes"),
            ("opt", plan.cost.per_chip_opt_bytes, "per_chip_opt_bytes"),
        ):
            live_bytes = result[live_key]
            result[f"predicted_{tree}_bytes"] = int(predicted)
            result[f"predicted_{tree}_error_pct"] = (
                abs(predicted - live_bytes) / live_bytes * 100.0 if live_bytes else 0.0
            )
        return result, losses

    dev0 = jax.devices()[0]
    # Grads live exactly where the params do (jax.grad output sharding follows
    # the param placement the step pins), so a placed zeros tree measures them.
    from accelerate_tpu.parallel.sharding import place_params

    grads = place_params(
        jax.tree_util.tree_map(lambda x: jax.numpy.zeros_like(x), model.params),
        model.param_compute_sharding,
    )
    result = {
        "mesh": mesh_axes,
        "steps": args.steps,
        "step_time_s_mean": wall / args.steps,
        "per_chip_param_bytes": int(tree_device_nbytes(model.params, dev0)),
        "per_chip_grad_bytes": int(tree_device_nbytes(grads, dev0)),
        "per_chip_opt_bytes": int(tree_device_nbytes(opt.opt_state, dev0)),
        "recompiles": guard.total_recompiles,
        "host_transfers": guard.host_transfers,
        "final_loss": losses[-1],
    }
    if mode == "2d":
        # Predicted-vs-live: re-run the (deterministic) planner the prepare()
        # seam ran and compare its per-chip account against the live bytes.
        from accelerate_tpu.parallel.planner import Workload, plan_sharding

        plan = plan_sharding(
            jax.eval_shape(lambda p: p, model.params),
            {k: v for k, v in dict(accelerator.mesh.shape).items() if k in ("data", "model")},
            axes=tuple(a for a in ("data", "model") if dict(accelerator.mesh.shape).get(a, 1) > 1),
            workload=Workload(batch=8, seq=512, opt_bytes_per_param=8.0),
        )
        for tree, predicted, live_key in (
            ("params", plan.cost.per_chip_param_bytes, "per_chip_param_bytes"),
            ("grads", plan.cost.per_chip_param_bytes, "per_chip_grad_bytes"),
            ("opt", plan.cost.per_chip_opt_bytes, "per_chip_opt_bytes"),
        ):
            live = result[live_key]
            result[f"predicted_{tree}_bytes"] = int(predicted)
            result[f"predicted_{tree}_error_pct"] = (
                abs(predicted - live) / live * 100.0 if live else 0.0
            )
    return result, losses


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--model", default="llama-tiny", help="named in-tree model")
    parser.add_argument("--steps", type=int, default=4, help="measured steps per pass")
    parser.add_argument("--warmup", type=int, default=2, help="warmup (compile) steps per pass")
    parser.add_argument("--seq-len", type=int, default=32)
    parser.add_argument("--global-batch", type=int, default=8,
                        help="global batch (must divide by the data axis of BOTH passes)")
    parser.add_argument("--tp", type=int, default=2, help="model-axis size of the 2d/3d passes")
    parser.add_argument("--pp", type=int, default=2,
                        help="pipeline-axis size of the 3d pass (--pipeline-ab)")
    parser.add_argument("--pipeline-ab", action="store_true",
                        help="A/B the 2D ZeRO plan against the 3D MPMD pipeline plan "
                             "(2d-vs-3d) instead of the default 1d-vs-2d")
    parser.add_argument("--loss-atol", type=float, default=2e-4,
                        help="per-step loss parity tolerance between the two passes")
    parser.add_argument("--mode", default="train", help=argparse.SUPPRESS)  # routing residue
    args = parser.parse_args(argv)

    import jax

    from accelerate_tpu.utils.environment import configure_compile_cache

    configure_compile_cache()
    n_chips = jax.device_count()
    log(f"backend: {n_chips}x {jax.devices()[0].device_kind}")

    baseline, contender = ("2d", "3d") if args.pipeline_ab else ("1d", "2d")
    results = {}
    losses = {}
    for mode in (baseline, contender):
        log(f"{mode} pass: {args.warmup}+{args.steps} steps, global batch {args.global_batch}...")
        results[mode], losses[mode] = run_pass(mode, args)
        log(f"{mode}: {results[mode]['step_time_s_mean'] * 1000:.1f} ms/step, "
            f"opt {results[mode]['per_chip_opt_bytes']} B/chip")

    # Loss-trajectory parity: same data, same init, same optimizer — the
    # parallel decomposition must not change the math.
    drift = max(abs(a - b) for a, b in zip(losses[baseline], losses[contender]))
    assert drift <= args.loss_atol, (
        f"{baseline}-vs-{contender} loss trajectories diverged (max |Δ| {drift:.2e} "
        f"> atol {args.loss_atol:.0e}): {losses[baseline]} vs {losses[contender]}"
    )

    device = jax.devices()[0].platform
    prefix = "" if device in ("tpu", "gpu") else "cpu-smoke "
    extra = {
        "device_kind": jax.devices()[0].device_kind,
        "platform": device,
        "loss_parity_max_drift": drift,
        f"loss_trajectory_{baseline}": losses[baseline],
        f"loss_trajectory_{contender}": losses[contender],
        baseline: results[baseline],
        contender: results[contender],
    }
    if args.pipeline_ab:
        # Headline: busiest-stage per-chip PARAM bytes under the 3D pipeline
        # plan — pipelining's memory win over the flat 2D mesh. The bubble
        # account (measured vs predicted) rides in extra["3d"]["pipeline"].
        par_2d = results["2d"]["per_chip_param_bytes"]
        par_3d = results["3d"]["per_chip_param_bytes"]
        row = {
            "metric": f"{prefix}per-chip param bytes, 3D MPMD pipeline plan "
            f"({args.model}, mesh {results['3d']['mesh']}, vs 2D ZeRO baseline)",
            "value": par_3d,
            "unit": "bytes/chip",
            # Ratio > 1: how many times less param HBM each chip holds.
            "vs_baseline": round(par_2d / max(par_3d, 1), 3),
            "extra": extra,
        }
    else:
        opt_1d = results["1d"]["per_chip_opt_bytes"]
        opt_2d = results["2d"]["per_chip_opt_bytes"]
        row = {
            "metric": f"{prefix}per-chip optimizer-state bytes, 2D ZeRO plan "
            f"({args.model}, mesh {results['2d']['mesh']}, vs 1D replicated baseline)",
            "value": opt_2d,
            "unit": "bytes/chip",
            # Ratio > 1: how many times less optimizer HBM each chip holds.
            "vs_baseline": round(opt_1d / max(opt_2d, 1), 3),
            "extra": extra,
        }
    print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
