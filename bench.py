"""Benchmark entry (driver contract): prints ONE JSON line
`{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}`.

Measures training throughput (samples/sec/chip) of BERT-base GLUE-style sequence
classification through the full framework path — prepared model, sharded dataloader,
fused train step — i.e. the same code a user runs, not a stripped kernel loop. That
matches BASELINE.json's metric ("samples/sec/chip (GLUE BERT ...)").

`vs_baseline` is measured MFU / 0.45 — the north-star gate from BASELINE.md ("≥45% MFU
... via a native XLA-SPMD backend"); >1.0 beats the target. On hosts where peak FLOPs
for the chip are unknown (e.g. CPU smoke runs) MFU is reported as null and vs_baseline
falls back to samples/sec normalized by a reference-epoch constant.

The measurement runs IN THIS PROCESS (one process owns the chip): a failure
propagates as a non-zero exit, and the script never re-executes itself under
another platform. It runs on CPU only when the caller forces JAX_PLATFORMS=cpu,
and then under the "cpu-smoke" metric name. Platform-dependent default sizes are
printed with the result (extra.sizes). All diagnostics go to stderr; stdout
carries exactly the one JSON line.
"""

import argparse
import json
import os
import sys
import time

import numpy as np


def log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _peak_memory_gb():
    """Peak device-memory use of the run (the reference benchmarks report peak
    memory alongside every number, benchmarks/measures_util.py) — None where
    the backend doesn't expose memory_stats (e.g. CPU)."""
    import jax

    try:
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        return round(peak / 2**30, 3) if peak else None
    except Exception:
        return None


def _last_attention_dispatch():
    from accelerate_tpu.ops import attention

    return attention.LAST_DISPATCH


def inference_bench(args):
    """Big-model-inference metric (reference benchmarks/big_model_inference.py:
    model load + per-token generation latency, README.md:27-37): reports p50 TTFT
    (compiled prefill) and per-token decode latency through the KV-cache path."""
    import jax

    from accelerate_tpu.generation import GenerationConfig, Generator

    on_accel = jax.devices()[0].platform in ("tpu", "gpu")
    families = ("llama", "gptj", "gpt-neox", "opt")
    model_name = args.model if args.model.startswith(families) else "llama-1b"
    if not on_accel:
        # CPU smoke: same family, tiny size.
        fam = next(f for f in families if model_name.startswith(f))
        model_name = f"{fam}-tiny"
    t_load = time.perf_counter()
    # Every decoder family in the reference's benchmark table (benchmarks/
    # README.md:27-37: GPT-J-6B headline 0.05 s/token fp16 on 2x Titan RTX,
    # GPT-NeoX-20B, OPT-30B) is constructible here; bf16 storage on accelerators.
    from accelerate_tpu.models import create_named_model, get_model_family

    _fam, cfg = get_model_family(model_name)
    model = create_named_model(
        model_name, seq_len=args.seq_len, param_dtype="bfloat16" if on_accel else None
    )
    load_s = time.perf_counter() - t_load

    batch = args.batch_size or 1
    prompt_len = min(args.seq_len, cfg.max_position_embeddings // 2)
    new_tokens = 32
    gen = Generator(model, max_new_tokens=new_tokens, max_length=prompt_len + new_tokens)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, cfg.vocab_size, (batch, prompt_len)).astype(np.int32)

    # Compile every program the timed sections use: prefill, the 1-token decode
    # (TTFT loop), and the full fused decode loop (compiled per max_new).
    jax.block_until_ready(gen(prompt, GenerationConfig(max_new_tokens=1)))
    jax.block_until_ready(gen(prompt, GenerationConfig(max_new_tokens=new_tokens)))

    ttfts = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(gen(prompt, GenerationConfig(max_new_tokens=1)))
        ttfts.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    jax.block_until_ready(gen(prompt, GenerationConfig(max_new_tokens=new_tokens)))
    total = time.perf_counter() - t0
    ttft_p50 = sorted(ttfts)[len(ttfts) // 2]
    per_token = (total - ttft_p50) / max(new_tokens - 1, 1)
    per_token_fallback = per_token <= 0
    if per_token_fallback:
        # Overhead-dominated run (tiny model on a noisy host): the median
        # 1-token TTFT exceeded the fused full-decode time. Fall back to the
        # whole-decode average (prefill amortized in — tagged in extra, and
        # never fed into the baseline ratio) rather than emitting a negative
        # latency.
        per_token = total / new_tokens

    # reference headline: GPT-J-6B fp16 on 2x Titan RTX = 0.05 s/token
    # (benchmarks/README.md:31); vs_baseline = reference / ours (higher is
    # better). The ratio is only apples-to-apples when the measured model IS
    # gpt-j-6b — for other sizes it is reported as 0 with the raw latency
    # left to speak for itself (a 1B model "beating" a 6B baseline is noise).
    metric = f"per-token generation latency ({model_name}, prompt {prompt_len}, bs {batch})"
    if on_accel and model_name.startswith("gptj-6b") and not per_token_fallback:
        vs_baseline = 0.05 / per_token if per_token > 0 else 0.0
    elif on_accel:
        vs_baseline = 0.0
    else:
        metric = "cpu-smoke " + metric
        vs_baseline = 0.0
    result = {
        "metric": metric,
        "value": round(per_token * 1000, 3),
        "unit": "ms/token",
        "vs_baseline": round(vs_baseline, 4),
        "extra": {
            "ttft_p50_ms": round(ttft_p50 * 1000, 3),
            "model_load_s": round(load_s, 2),
            "device_kind": jax.devices()[0].device_kind,
            "platform": jax.devices()[0].platform,
            "sizes": {"platform_defaults": {"model": model_name}, "prompt_len": prompt_len,
                      "batch": batch},
            "new_tokens": new_tokens,
        },
    }
    if on_accel and not model_name.startswith("gptj-6b"):
        # Distinguish "ratio suppressed" from the CPU-fallback convention of
        # vs_baseline == 0 (docs/concepts/performance.md): this IS a real
        # accelerator number, just not size-matched to the 6B baseline.
        result["extra"]["baseline_note"] = "ratio suppressed: baseline model is gptj-6b"
    if per_token_fallback:
        result["extra"]["per_token_fallback"] = True
    print(json.dumps(result))


def train_bench(args):
    import jax
    import optax

    from accelerate_tpu import Accelerator, SimpleDataLoader
    from accelerate_tpu.data_loader import BatchSampler
    from accelerate_tpu.utils.environment import get_device_peak_flops

    t0 = time.time()
    n_chips = jax.device_count()
    device_kind = jax.devices()[0].device_kind
    on_accel = jax.devices()[0].platform in ("tpu", "gpu")
    log(f"backend up in {time.time() - t0:.1f}s: {n_chips}x {device_kind}")

    compilation_config = None
    if args.remat:
        from accelerate_tpu.utils import CompilationConfig

        compilation_config = CompilationConfig(remat_policy=args.remat)
    fsdp_plugin = None
    if args.param_dtype:
        # Storage-dtype knob (FSDP plugin; a 1-chip fsdp axis shards nothing
        # but the dtype policy still applies): bf16 params+moments halve the
        # optimizer-state HBM — fp32 AdamW moments alone are ~12 GB at 1B
        # params, which is what OOM'd the round-4 llama-1b no-remat legs.
        from accelerate_tpu.utils import FullyShardedDataParallelPlugin

        fsdp_plugin = FullyShardedDataParallelPlugin(param_dtype=args.param_dtype)
    accelerator = Accelerator(
        mixed_precision=args.mixed_precision,
        compilation_config=compilation_config,
        fsdp_plugin=fsdp_plugin,
    )
    # Report the dtype the plugin actually APPLIED, not the CLI flag: the
    # ACCELERATE_TPU_FSDP_PARAM_DTYPE env protocol overrides the constructor
    # arg in __post_init__, and a mislabeled row would corrupt the bf16-moments
    # A/B evidence.
    effective_param_dtype = (
        getattr(accelerator.state.fsdp_plugin, "param_dtype", None) or "float32"
    )

    # Defaults that depend on the platform, printed with the result (extra.sizes)
    # so a run always says which size it took.
    sizes_from_platform = {}
    if args.batch_size is None:
        # Headline per-chip batch. BASELINE.md's north star is an MFU floor
        # (>= 0.45), not a fixed batch; 64/chip is the standard BERT-base
        # seq-128 fine-tune size for a 16 GB chip and the best point of the
        # builder-side hardware sweep (bench_suite_r04.jsonl: MFU 0.335 @ bs 32 /
        # 0.502 @ bs 64 / 0.469 @ bs 128 at equal 500-step regions — bs 32
        # steps are too short to hide the per-call host dispatch).
        args.batch_size = 64 if on_accel else 4
        sizes_from_platform["batch_size"] = args.batch_size
    if args.steps_per_call is None:
        # Auto: small-step configs (bert-base seq 128 runs ~10-40ms/step on one
        # chip) pay one host dispatch PER STEP; the scanned
        # device loop (train_step(steps_per_call=K)) pays it once per K steps.
        # Big-step models (llama seq>=1024, ~300ms/step) don't need it. The
        # eager path ignores the knob, and --per_step_readback is a per-STEP
        # sync validation mode — both keep one step per call.
        auto_loop = on_accel and args.model.startswith("bert")
        args.steps_per_call = 10 if (auto_loop and not args.eager and not args.per_step_readback) else 1
        sizes_from_platform["steps_per_call"] = args.steps_per_call
    if args.eager and args.steps_per_call > 1:
        log("eager path ignores steps_per_call; forcing 1")
        args.steps_per_call = 1
    if args.per_step_readback and args.steps_per_call > 1:
        log("--per_step_readback syncs every step; forcing steps_per_call=1")
        args.steps_per_call = 1
    spc = max(1, args.steps_per_call)
    if args.steps % spc:
        args.steps = (args.steps // spc + 1) * spc
        log(f"steps rounded up to {args.steps} (multiple of steps_per_call={spc})")

    if args.model.startswith("bert"):
        from accelerate_tpu.models import bert_base, bert_tiny, create_bert_model

        cfg = bert_base() if args.model == "bert-base" else bert_tiny()
        model = create_bert_model(cfg, seq_len=args.seq_len)
        rng = np.random.default_rng(0)
        global_batch = args.batch_size * n_chips
        # Enough data that the timed region is ONE continuous loader pass: epoch
        # restarts tear down the prefetch thread and stall the device every
        # 2 steps otherwise, which benchmarks the restart cost, not training.
        n = global_batch * (args.trials * args.steps + (args.warmup + 2) * spc + 2)
        data = [
            {
                "input_ids": rng.integers(1, cfg.vocab_size, size=(args.seq_len,)).astype(np.int32),
                "labels": np.int64(rng.integers(0, cfg.num_labels)),
            }
            for _ in range(n)
        ]
        hidden = cfg.hidden_size
        vocab = cfg.vocab_size
    else:
        if args.model.startswith("gptj"):
            from accelerate_tpu.models.gptj import create_gptj_model, gptj_tiny

            cfg = gptj_tiny()
            model = create_gptj_model(cfg, seq_len=args.seq_len)
        else:
            from accelerate_tpu.models.llama import create_llama_model, llama_1b, llama_tiny

            cfg = llama_1b() if args.model == "llama-1b" else llama_tiny()
            model = create_llama_model(cfg, seq_len=args.seq_len)
        rng = np.random.default_rng(0)
        global_batch = args.batch_size * n_chips
        n = global_batch * (args.trials * args.steps + (args.warmup + 2) * spc + 2)
        data = [
            {"input_ids": rng.integers(1, cfg.vocab_size, size=(args.seq_len,)).astype(np.int32)} for _ in range(n)
        ]
        hidden = cfg.hidden_size
        vocab = cfg.vocab_size

    # The device-loop mode consumes spc step-batches per call: the loader
    # collates them as ONE [spc*global_batch] array (one transfer per call).
    dl = SimpleDataLoader(data, BatchSampler(range(n), global_batch * spc, drop_last=True))
    pmodel, popt, pdl = accelerator.prepare(model, optax.adamw(1e-4), dl)
    param_count = pmodel.num_parameters

    def batches():
        while True:
            for b in pdl:
                yield b

    stream = batches()

    # Telemetry (docs/observability.md): phase-split the bench loop through the
    # Accelerator's own StepTimeline — data-wait vs dispatch vs explicit
    # readback — and charge backend-compile durations to the goodput ledger so
    # the emitted JSON says where the wall clock went (the r05 hang was
    # invisible precisely because nothing recorded this).
    timeline = accelerator.timeline
    timeline.attach_compile_listener()

    if args.eager:

        def run_steps(n):
            last_loss = None
            for _ in range(n):
                with timeline.phase("data_wait"):
                    batch = next(stream)
                with accelerator.accumulate(pmodel):
                    with timeline.phase("dispatch"):
                        last_loss = accelerator.backward(pmodel.loss, batch)
                        popt.step()
                        popt.zero_grad()
                if args.per_step_readback:
                    with timeline.phase("block"):
                        float(last_loss)
                timeline.step_done()
            return last_loss

    else:
        # train_step() is already timeline-instrumented (dispatch + step_done)
        # by the Accelerator; only the data wait needs marking here.
        step_fn = accelerator.train_step(steps_per_call=spc)

        def run_steps(n):
            last_loss = None
            # n is a step count, always a multiple of spc (steps are rounded up
            # at parse time, warmup is passed as warmup*spc).
            for _ in range(n // spc):
                with timeline.phase("data_wait"):
                    batch = next(stream)
                last_loss = step_fn(batch)
                if args.per_step_readback:
                    # step_fn already closed the step (step_done inside the
                    # Accelerator shim): record_phase attributes the readback
                    # without reopening it.
                    t_block = time.perf_counter()
                    float(last_loss)
                    timeline.record_phase("block", time.perf_counter() - t_block)
            return last_loss

    # Warmup (compile)
    t0 = time.time()
    run_steps(args.warmup * spc)
    jax.block_until_ready(pmodel.params)
    log(f"warmup+compile {time.time() - t0:.1f}s")

    # Timed. Every region ends in jax.block_until_ready — the ONE fence: it waits
    # for the device (chip_smoke.py's device phase holds it to the FLOPs/peak
    # floor on every run). --per_step_readback re-measures with a
    # sync after every step to validate the pipelined number (it adds one host
    # round-trip per step, so it lower-bounds rather than reproduces it).
    # Median of `--trials` regions: robust to a one-off stall in either direction.
    elapsed_trials = []
    loss = None
    for _ in range(args.trials):
        t0 = time.perf_counter()
        loss = run_steps(args.steps)
        jax.block_until_ready(pmodel.params)
        elapsed_trials.append(time.perf_counter() - t0)
    final_loss = float(loss) if loss is not None else None
    elapsed = sorted(elapsed_trials)[len(elapsed_trials) // 2]
    steps_done = args.steps

    samples = steps_done * global_batch
    samples_per_sec = samples / elapsed
    samples_per_sec_per_chip = samples_per_sec / n_chips

    # Training FLOPs ≈ 6 * non-embedding-params * tokens (fwd 2x + bwd 4x),
    # standard transformer accounting.
    embed_params = vocab * hidden
    flops_per_token = 6 * max(param_count - embed_params, 1)
    tokens_per_sec = samples_per_sec * args.seq_len
    model_flops_per_sec = flops_per_token * tokens_per_sec
    # A CPU run is a smoke run with no MFU; on an accelerator an unknown
    # device_kind is an error (get_device_peak_flops raises), never a silent null.
    mfu = None
    if on_accel:
        mfu = model_flops_per_sec / (get_device_peak_flops(device_kind) * n_chips)
    if mfu is not None and mfu > 1.0:
        # MFU above 1.0 is physically impossible — it means the timing fence
        # failed and we measured dispatch, not execution. Refuse to publish it.
        raise RuntimeError(
            f"measured MFU {mfu:.3f} > 1.0 — timing fence failed (dispatch-only "
            f"measurement); refusing to emit an invalid benchmark number"
        )

    # Tag by the ACTUAL platform: a run on the CPU backend must never emit an
    # untagged chip number or a nonzero baseline ratio.
    metric = f"samples/sec/chip ({args.model}, seq {args.seq_len}, bs {args.batch_size}/chip, {args.mixed_precision})"
    if mfu is not None:
        vs_baseline = mfu / 0.45
    else:
        metric = "cpu-smoke " + metric
        vs_baseline = 0.0

    # Telemetry block: whole-run (warmup + all trials) phase accounting. The
    # goodput ledger's "compile" entry is the warmup's trace+compile cost; a
    # large unaccounted_s with small phase sums is the r05 signature (the host
    # stalled OUTSIDE the instrumented loop, e.g. backend init).
    def _phase_ms(name):
        hist = accelerator.telemetry.get(f"train_{name}_seconds")
        if hist is None or hist.count == 0:
            return None
        return {
            "count": hist.count,
            "p50_ms": round((hist.quantile(0.5) or 0.0) * 1000, 3),
            "p99_ms": round((hist.quantile(0.99) or 0.0) * 1000, 3),
        }

    phase_stats = {
        name: _phase_ms(name) for name in ("data_wait", "dispatch", "block", "step")
    }
    telemetry_block = {
        "goodput": timeline.goodput(),
        "phases": {name: stats for name, stats in phase_stats.items() if stats is not None},
    }

    result = {
        "metric": metric,
        "value": round(samples_per_sec_per_chip, 3),
        "unit": "samples/sec/chip",
        "vs_baseline": round(vs_baseline, 4),
        "extra": {
            "telemetry": telemetry_block,
            "device_kind": device_kind,
            "platform": jax.devices()[0].platform,
            "sizes": {"platform_defaults": sizes_from_platform, "steps": steps_done},
            "n_chips": n_chips,
            "mfu": round(mfu, 4) if mfu is not None else None,
            "tokens_per_sec": round(tokens_per_sec, 1),
            "params": param_count,
            "final_loss": final_loss,
            "steps": steps_done,
            "path": "eager" if args.eager else "fused",
            "steps_per_call": spc,
            "param_dtype": effective_param_dtype,
            "peak_hbm_gb": _peak_memory_gb(),
            # Which attention implementation the model's trace actually used —
            # proves (or disproves) that the flash kernel is on the measured path.
            "attention_impl": _last_attention_dispatch(),
        },
    }
    print(json.dumps(result))


def parse_args(argv):
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--model",
        default="bert-base",
        choices=[
            "bert-base",
            "bert-tiny",
            "llama-1b",
            "llama-tiny",
            "gptj-6b",
            "gptj-tiny",
            "gpt-neox-20b",
            "gpt-neox-tiny",
            "opt-30b",
            "opt-tiny",
        ],
    )
    parser.add_argument("--mode", default="train", choices=["train", "inference", "serving"])
    parser.add_argument("--batch_size", type=int, default=None, help="per-chip batch size")
    parser.add_argument("--seq_len", type=int, default=128)
    # 500-step default: a sustained region (round-3 verdict: 100-step windows
    # leave the headline sensitive to warmup/stall artifacts).
    parser.add_argument("--steps", type=int, default=500)
    parser.add_argument("--warmup", type=int, default=5)
    parser.add_argument(
        "--steps_per_call",
        type=int,
        default=None,
        help="optimizer steps scanned per compiled call (device training loop); "
        "default: 10 for bert on accelerators, else 1",
    )
    parser.add_argument(
        "--attention",
        default="auto",
        choices=["auto", "xla", "flash"],
        help="force the attention implementation on the measured path (A/B the "
        "Pallas flash kernel against the XLA path at seq >= 1024); 'auto' keeps "
        "the dispatcher's choice",
    )
    parser.add_argument("--trials", type=int, default=3, help="timed regions; the median is reported")
    parser.add_argument("--mixed_precision", default="bf16")
    parser.add_argument(
        "--remat",
        default=None,
        choices=["full", "dots"],
        help="per-layer activation checkpointing policy (HBM-tight configs)",
    )
    parser.add_argument(
        "--param_dtype",
        default=None,
        choices=["float32", "bfloat16"],
        help="param/optimizer-moment storage dtype (FSDP plugin knob; bf16 "
        "halves optimizer-state HBM so llama-1b seq-1024 fits the 16 GB chip)",
    )
    parser.add_argument("--eager", action="store_true", help="use the eager backward/step path instead of the fused step")
    parser.add_argument(
        "--per_step_readback",
        action="store_true",
        help="force a host readback after every step (validation mode for the timing fence)",
    )
    return parser.parse_args(argv)


def main():
    argv = sys.argv[1:]
    # --mode serving is routed BEFORE parse_args: the serving bench has its own
    # argument surface (workload shape, slots, chunk — benchmarks/serving_bench.py)
    # that this parser would reject. A pre-parser shares argparse's tokenization
    # (--mode X, --mode=X) and hands the serving bench everything else.
    pre = argparse.ArgumentParser(add_help=False)
    pre.add_argument("--mode")
    pre.add_argument("--zero-ab", action="store_true")
    pre.add_argument("--pipeline-ab", action="store_true")
    known, rest = pre.parse_known_args(argv)
    if known.zero_ab or known.pipeline_ab:
        # Training A/Bs (benchmarks/train_bench.py): 1D-replicated vs 2D-ZeRO
        # (--zero-ab) or 2D-ZeRO vs 3D-MPMD-pipeline (--pipeline-ab) — their
        # own argument surface, same pre-routing as serving/checkpoint.
        if known.mode not in (None, "train"):
            raise SystemExit("--zero-ab/--pipeline-ab are --mode train A/Bs")
        from benchmarks.train_bench import main as train_ab_main

        sys.exit(train_ab_main(rest + (["--pipeline-ab"] if known.pipeline_ab else [])))
    if known.mode == "serving":
        from benchmarks.serving_bench import main as serving_main

        sys.exit(serving_main(rest))
    if known.mode == "checkpoint":
        # Same pre-routing as serving: the checkpoint bench (sync vs async
        # save_state A/B, benchmarks/checkpoint_bench.py) owns its own args.
        from benchmarks.checkpoint_bench import main as checkpoint_main

        sys.exit(checkpoint_main(rest))
    args = parse_args(argv)
    if args.mode == "train" and args.model in ("gptj-6b", "gpt-neox-20b", "opt-30b"):
        # These sizes can't TRAIN on one 16GB chip (params + Adam state alone
        # exceed HBM); they exist for --mode inference, where they are the
        # reference benchmark's own models. Checked BEFORE any jax import.
        raise SystemExit(
            f"{args.model} is inference-only on a single chip: "
            f"run `python bench.py --mode inference --model {args.model}`"
        )
    if args.attention == "flash" and args.mode == "inference":
        # The decode path always threads a KV-cache mask, which the flash kernel
        # rejects by design — the A/B flag is for training benches.
        raise SystemExit("--attention flash applies to --mode train only (decode always carries a mask)")
    if args.attention != "auto":
        os.environ["ACCELERATE_TPU_ATTENTION_IMPL"] = args.attention
    from accelerate_tpu.utils.environment import configure_compile_cache

    configure_compile_cache()
    if args.mode == "inference":
        return inference_bench(args)
    return train_bench(args)


if __name__ == "__main__":
    main()
