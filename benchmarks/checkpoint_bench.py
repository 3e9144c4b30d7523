"""Checkpoint benchmark: synchronous vs asynchronous (snapshot-then-commit)
save_state, measured through the goodput ledger.

Workload: a tiny regression train loop whose model carries `--ballast-mb` of
incompressible parameters, so each checkpoint pays a REAL serialize+fsync cost.
Both passes run the same steps and save every step through the same
`CheckpointManager` pipeline; the only difference is the `async_save` knob:

  - **sync**: the step blocks for the full serialize+fsync+publish — every
    second lands in the goodput ledger's ``checkpoint`` cause
    (``lost_checkpoint_s``).
  - **async**: the step blocks only for the device->host snapshot (plus a
    barrier when the previous commit is still in flight); the commit pipeline
    runs on the background committer and reports through
    ``checkpoint_async_commit_seconds`` — measured separately, NOT lost time.

Emits exactly ONE JSON line on stdout (the bench-driver contract): headline is
per-save BLOCKING seconds under async, `vs_baseline` is the sync/async blocking
ratio (how many times less train time each save steals), and `extra` carries
both passes' ledgers — blocking per save, async commit seconds, goodput.

CPU smoke by default; `python bench.py --mode checkpoint` routes here.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time


def log(msg):
    print(f"[checkpoint-bench] {msg}", file=sys.stderr, flush=True)


def build_workload(base_dir, ballast_mb, async_save, keep_last_n=3):
    import numpy as np
    import optax

    import jax.numpy as jnp
    from accelerate_tpu import Accelerator, SimpleDataLoader
    from accelerate_tpu.data_loader import BatchSampler
    from accelerate_tpu.modeling import Model
    from accelerate_tpu.test_utils.training import RegressionDataset
    from accelerate_tpu.utils import ProjectConfiguration

    accelerator = Accelerator(
        project_config=ProjectConfiguration(
            project_dir=str(base_dir), automatic_checkpoint_naming=True, total_limit=keep_last_n
        ),
        async_save=async_save,
    )
    # Ballast: incompressible float32 params so the npz serialize pays real
    # compression + fsync cost proportional to --ballast-mb.
    n = max(1, int(ballast_mb * (1 << 20)) // 4)
    rng = np.random.default_rng(0)
    params = {
        "w": np.zeros((1, 1), np.float32),
        "b": np.zeros((1,), np.float32),
        "ballast": rng.standard_normal((n,)).astype(np.float32),
    }

    def apply_fn(p, x):
        return x[:, None] * p["w"] + p["b"]

    def loss_fn(p, batch):
        pred = apply_fn(p, batch["x"][:, 0])
        # 0-weight ballast term keeps its gradient defined (and zero).
        return jnp.mean((pred[:, 0] - batch["y"]) ** 2) + 0.0 * p["ballast"][0]

    model = Model.from_fn(apply_fn, params, loss_fn=loss_fn)
    data = [RegressionDataset(length=16, seed=0)[i] for i in range(16)]
    dl = SimpleDataLoader(data, BatchSampler(range(16), 8))
    model, opt, pdl = accelerator.prepare(model, optax.sgd(0.05), dl)
    return accelerator, model, opt, pdl


def run_pass(base_dir, steps, ballast_mb, async_save, step_s=0.0, save_every=1):
    """One measured pass: N steps, one save_state per step. Returns the ledger
    the comparison is made of."""
    accelerator, model, opt, pdl = build_workload(base_dir, ballast_mb, async_save)
    stream = iter(lambda: None, 1)  # placeholder; rebuilt below

    def batches():
        while True:
            for b in pdl:
                yield b

    stream = batches()
    # Warm the train step (compiles) before the timed region.
    batch = next(stream)
    accelerator.backward(model.loss_fn, batch)
    opt.step()
    opt.zero_grad()
    accelerator.timeline.reset()

    save_block_s = []
    t0 = time.perf_counter()
    for _step in range(steps):
        batch = next(stream)
        accelerator.backward(model.loss_fn, batch)
        opt.step()
        opt.zero_grad()
        if step_s:
            # Simulated device-compute per step: the window a background commit
            # overlaps with. The regression model's real step is microseconds;
            # without this the A/B degenerates to back-to-back saves where the
            # next save's barrier absorbs the whole commit — the worst case,
            # not the training case.
            time.sleep(step_s)
        if (_step + 1) % save_every:
            continue
        s0 = time.perf_counter()
        accelerator.save_state()
        save_block_s.append(time.perf_counter() - s0)
    wall_to_last_save = time.perf_counter() - t0
    d0 = time.perf_counter()
    accelerator.drain_checkpoints()
    drain_s = time.perf_counter() - d0
    stream.close()
    goodput = accelerator.timeline.goodput()
    commit_hist = accelerator._m_ckpt_commit_seconds
    return {
        "steps": steps,
        "saves": len(save_block_s),
        "save_blocking_s_mean": sum(save_block_s) / len(save_block_s),
        "save_blocking_s_max": max(save_block_s),
        "lost_checkpoint_s": goodput["lost_s"].get("checkpoint", 0.0),
        "lost_checkpoint_s_per_save": goodput["lost_s"].get("checkpoint", 0.0) / len(save_block_s),
        "checkpoint_async_commit_s": commit_hist.sum,
        "async_commits": commit_hist.count,
        "final_drain_s": drain_s,
        "wall_to_last_save_s": wall_to_last_save,
        "goodput": goodput,
    }


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--steps", type=int, default=6, help="train steps")
    parser.add_argument("--save-every", type=int, default=2, help="save_state every N steps")
    parser.add_argument("--step-ms", type=float, default=400.0,
                        help="simulated device compute per step (the commit-overlap window); "
                        "0 measures the degenerate back-to-back-saves worst case")
    parser.add_argument("--ballast-mb", type=float, default=8.0,
                        help="incompressible parameter ballast per checkpoint (MiB)")
    parser.add_argument("--base-dir", default=None,
                        help="checkpoint root (default: a temp dir, cleaned up)")
    args = parser.parse_args(argv)
    if args.steps < max(args.save_every, 1):
        parser.error(
            f"--steps {args.steps} < --save-every {args.save_every}: the run would never save"
        )

    from accelerate_tpu.utils.environment import configure_compile_cache

    configure_compile_cache()
    scratch = args.base_dir or tempfile.mkdtemp(prefix="accelerate_tpu_ckpt_bench_")
    try:
        results = {}
        for mode in ("sync", "async"):
            base = os.path.join(scratch, mode)
            log(f"{mode} pass: {args.steps} steps ({args.step_ms:g} ms each) x "
                f"{args.ballast_mb} MiB ballast, save every {args.save_every}...")
            results[mode] = run_pass(base, args.steps, args.ballast_mb, mode == "async",
                                     step_s=args.step_ms / 1000.0, save_every=max(args.save_every, 1))
            log(
                f"{mode}: blocking/save {results[mode]['save_blocking_s_mean'] * 1000:.1f} ms, "
                f"lost_checkpoint_s {results[mode]['lost_checkpoint_s']:.3f}, "
                f"async commit {results[mode]['checkpoint_async_commit_s']:.3f}s"
            )
    finally:
        if args.base_dir is None:
            shutil.rmtree(scratch, ignore_errors=True)

    sync_block = results["sync"]["lost_checkpoint_s_per_save"]
    async_block = results["async"]["lost_checkpoint_s_per_save"]
    import jax

    device = jax.devices()[0].platform
    prefix = "cpu-smoke " if device == "cpu" else ""
    row = {
        "metric": f"{prefix}blocking checkpoint seconds per save, async (vs sync baseline, "
        f"{args.ballast_mb:g} MiB state)",
        "value": round(async_block, 6),
        "unit": "s/save blocking",
        # Ratio > 1: how many times LESS step time each async save steals.
        "vs_baseline": round(sync_block / max(async_block, 1e-9), 3),
        "extra": {
            "device_kind": device,
            "ballast_mb": args.ballast_mb,
            "step_ms": args.step_ms,
            "save_every": args.save_every,
            "sync": {k: (round(v, 6) if isinstance(v, float) else v)
                     for k, v in results["sync"].items() if k != "goodput"},
            "async": {k: (round(v, 6) if isinstance(v, float) else v)
                      for k, v in results["async"].items() if k != "goodput"},
            "goodput_sync": results["sync"]["goodput"],
            "goodput_async": results["async"]["goodput"],
        },
    }
    print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
