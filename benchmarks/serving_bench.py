"""Serving benchmark: static batching vs continuous (slot-based) batching on a
mixed-length synthetic workload.

Workload: `--requests` prompts with uniform lengths in [--prompt-min,
--prompt-max], budgets in [--max-new-min, --max-new-max], Poisson arrivals
(exponential inter-arrival, mean --mean-interarrival seconds). Both paths serve
the SAME workload greedily on the same model and are timed against a virtual
clock that advances by measured compute, so arrival gating is identical and
deterministic modulo host timing noise.

  - **static**: requests are batched `num_slots` at a time in arrival order
    (left-padded to the batch's prompt bucket) through the fused `Generator`
    loop; a batch runs to its LONGEST budget before the next one starts — the
    convoy effect this PR removes.
  - **continuous**: the same requests stream through `serving.ContinuousBatcher`
    (insert-into-free-slot + chunked decode), late arrivals joining mid-flight.

Emits exactly ONE JSON line on stdout (the bench-driver contract): headline is
continuous-batching tokens/sec, with static/continuous tokens/sec, TTFT p50/p99,
and total decode-loop iterations for both paths in `extra`.

CPU smoke sizes by default off-accelerator; `python bench.py --mode serving`
routes here.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time

import numpy as np


def log(msg):
    print(f"[serving-bench] {msg}", file=sys.stderr, flush=True)


def build_workload(args, vocab_size, rng):
    prompts = [
        rng.integers(1, vocab_size, (int(rng.integers(args.prompt_min, args.prompt_max + 1)),)).astype(np.int32)
        for _ in range(args.requests)
    ]
    budgets = [int(rng.integers(args.max_new_min, args.max_new_max + 1)) for _ in range(args.requests)]
    arrivals = np.cumsum(rng.exponential(args.mean_interarrival, size=args.requests))
    return prompts, budgets, arrivals


def run_static(gen, prompts, budgets, arrivals, num_slots, max_length):
    """Arrival-order batches of `num_slots` through the fused Generator; returns
    (tokens_per_sec, ttfts, decode_iterations, makespan). `gen` is reused across
    warmup and timed passes so the timed pass runs warm executables."""
    import jax.numpy as jnp

    from accelerate_tpu.generation import GenerationConfig, _bucket_for

    clock = 0.0
    ttfts, decode_iterations = [], 0
    n = len(prompts)
    for start in range(0, n, num_slots):
        idx = list(range(start, min(start + num_slots, n)))
        batch_prompts = [prompts[i] for i in idx]
        batch_new = max(budgets[i] for i in idx)
        width = min(_bucket_for(max(p.size for p in batch_prompts)), max_length - batch_new)
        ids = np.zeros((len(idx), width), np.int32)
        mask = np.zeros((len(idx), width), np.int32)
        for r, p in enumerate(batch_prompts):
            ids[r, width - p.size:] = p  # LEFT padding (the Generator convention)
            mask[r, width - p.size:] = 1
        ids, mask = jnp.asarray(ids), jnp.asarray(mask)
        # the whole batch must have arrived before its prefill can start
        clock = max(clock, float(arrivals[idx[-1]]))
        # TTFT component: a 1-token run isolates prefill+first-token latency
        # (measured outside the clock; the real serving time is the full run)
        t0 = time.perf_counter()
        np.asarray(gen(ids, GenerationConfig(max_new_tokens=1), attention_mask=mask))
        t_first = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.asarray(gen(ids, GenerationConfig(max_new_tokens=batch_new), attention_mask=mask))
        t_full = time.perf_counter() - t0
        for i in idx:
            ttfts.append(clock - float(arrivals[i]) + t_first)
        clock += t_full
        # greedy, no EOS: the fused while_loop runs exactly (batch_new - 1)
        # body iterations (the first token comes from prefill)
        decode_iterations += batch_new - 1
    useful = sum(budgets)
    makespan = clock - float(arrivals[0])
    return useful / max(makespan, 1e-9), ttfts, decode_iterations, makespan


def run_continuous(engine, prompts, budgets, arrivals, collect_tokens=None):
    """The same workload through the slot engine; arrival-gated submission on
    the virtual clock. Returns (tokens_per_sec, ttfts, decode_iterations,
    makespan). Finished requests are `release()`d at the end, so the engine is
    reusable across warmup and timed passes with the same request ids.
    `collect_tokens` (a dict) captures each request's generated tokens before
    release — the quant A/B compares token streams across engines with it."""
    from accelerate_tpu.serving import Request

    clock = 0.0
    n = len(prompts)
    submitted = 0
    first_seen = {}
    base_steps = engine.stats["decode_steps"]
    while submitted < n or engine.pending:
        while submitted < n and float(arrivals[submitted]) <= clock:
            engine.submit(Request(submitted, prompts[submitted], max_new_tokens=budgets[submitted]))
            submitted += 1
        if not engine.pending:
            clock = float(arrivals[submitted])  # idle until the next arrival
            continue
        t0 = time.perf_counter()
        events = engine.step()
        clock += time.perf_counter() - t0
        for rid, _toks in events:
            first_seen.setdefault(rid, clock)
    ttfts = [first_seen[i] - float(arrivals[i]) for i in range(n)]
    useful = sum(budgets)
    makespan = clock - float(arrivals[0])
    for i in range(n):
        if collect_tokens is not None:
            collect_tokens[i] = [int(t) for t in engine.results[i].tokens]
        engine.release(i)
    return (
        useful / max(makespan, 1e-9),
        ttfts,
        engine.stats["decode_steps"] - base_steps,
        makespan,
    )


def pct(values, q):
    return float(np.percentile(np.asarray(values), q))


def run_router_workload(model, args, cfg, max_length, rng, tracer=None):
    """The replicated-fleet A/B (`--replicas N`): the mixed workload served
    through a `router.Router` over N engines — once clean (baseline), once
    with replica 0 killed mid-traffic (the chaos-kill shape, through the
    router's ops seam so the engine's warm executables are reused on rejoin).
    Reports throughput for both passes, the dip during the degraded window,
    and the measured recovery time (kill -> replica live again), under the
    same hard 0-recompile / 0-host-transfer gate as the single-engine passes
    (one process-wide TraceGuard: zero total means zero per engine)."""
    from accelerate_tpu.analysis import TraceGuard
    from accelerate_tpu.router import Router
    from accelerate_tpu.serving import Request

    prompts, budgets, arrivals = build_workload(args, cfg.vocab_size, rng)
    router = Router(
        model, replicas=args.replicas, num_slots=args.num_slots,
        max_length=max_length, chunk_size=args.chunk_size,
        max_queue=args.requests + 16, default_deadline_s=600.0,
        page_size=args.page_size, tracer=tracer,
        rejoin_cooldown_s=0.2, probation_steps=1, stall_degrade_s=None,
        attention_impl=args.attention_impl,
        weight_dtype=args.weight_dtype, kv_cache_dtype=args.kv_cache_dtype,
    )

    def run_traffic(kill_fraction=None):
        """Arrival-gated traffic on the virtual clock. With `kill_fraction`,
        replica 0 is failed once that fraction of requests has finished;
        returns per-pass measurements including the kill/recovery marks."""
        clock = 0.0
        n = len(prompts)
        submitted = 0
        first_seen = {}
        token_marks = []  # (virtual clock, tokens streamed in this event)
        killed = False
        kill_clock = recover_clock = None
        kill_wall = recover_wall = None
        while submitted < n or router.pending or (killed and recover_wall is None):
            while submitted < n and float(arrivals[submitted]) <= clock:
                router.submit(Request(submitted, prompts[submitted],
                                      max_new_tokens=budgets[submitted]))
                submitted += 1
            if not router.pending and submitted < n:
                clock = float(arrivals[submitted])
                continue
            t0 = time.perf_counter()
            events = router.step()
            clock += time.perf_counter() - t0
            for rid, toks in events:
                first_seen.setdefault(rid, clock)
                token_marks.append((clock, len(toks)))
            if kill_fraction is not None and not killed and submitted == n:
                finished = sum(router.results[i].finished for i in range(n))
                if finished >= n * kill_fraction:
                    killed = True
                    kill_clock, kill_wall = clock, time.perf_counter()
                    log(f"kill A/B: failing replica 0 after {finished}/{n} requests")
                    router.fail_replica(0, reason="bench kill A/B", dead=False)
            if killed and recover_wall is None and router.replica_states[0] == "live":
                recover_clock, recover_wall = clock, time.perf_counter()
            if killed and recover_wall is None and not router.pending:
                time.sleep(0.02)  # idle: let the rejoin cooldown elapse
        delivered = sum(len(router.results[i].tokens) for i in range(n))
        reasons = {}
        for i in range(n):
            reason = router.results[i].finish_reason
            reasons[reason] = reasons.get(reason, 0) + 1
        ttfts = [first_seen.get(i, clock) - float(arrivals[i]) for i in range(n)]
        makespan = clock - float(arrivals[0])
        out = {
            "tokens_per_sec": round(delivered / max(makespan, 1e-9), 2),
            "tokens_delivered": delivered,
            "ttft_p50_ms": round(pct(ttfts, 50) * 1000, 2),
            "ttft_p99_ms": round(pct(ttfts, 99) * 1000, 2),
            "makespan_s": round(makespan, 3),
            "finish_reasons": reasons,
        }
        if killed:
            out["recovery_s"] = (
                round(recover_wall - kill_wall, 3) if recover_wall is not None else None
            )
            if recover_clock is not None and recover_clock > kill_clock:
                window = [t for t in token_marks if kill_clock <= t[0] <= recover_clock]
                out["degraded_window_tokens_per_sec"] = round(
                    sum(c for _, c in window) / (recover_clock - kill_clock), 2
                )
        for i in range(n):
            router.release(i)
        return out

    log(f"router workload ({args.replicas} replicas): warmup...")
    warmed = router.warm_inserts()
    log(f"router insert buckets warmed: {sorted(set(sum(warmed.values(), [])))}")
    run_traffic()
    run_traffic()
    guard = TraceGuard(
        transfer_guard="disallow", on_violation="record", name="serving-bench-router"
    )
    with guard:
        baseline = run_traffic()
        killed = run_traffic(kill_fraction=1 / 3)
    if guard.total_recompiles or guard.host_transfers:
        log(f"TRACE-GUARD VIOLATIONS in router workload: {guard.report().summary()}")
    # The fleet pin: routing, retry, soft-kill recovery and rejoin must all
    # reuse the warm per-engine executables — 0 recompiles, 0 host transfers
    # across every engine (a process-wide zero is a per-engine zero).
    assert guard.total_recompiles == 0 and guard.host_transfers == 0, (
        "router workload regressed the 0-recompile / 0-host-transfer discipline: "
        f"{guard.report().summary()}"
    )
    stats = router.stats
    result = {
        "replicas": args.replicas,
        "baseline": baseline,
        "kill_ab": killed,
        "throughput_dip_ratio": round(
            killed["tokens_per_sec"] / max(baseline["tokens_per_sec"], 1e-9), 3
        ),
        "recovery_s": killed.get("recovery_s"),
        "retries": stats["retries"],
        "ejected": stats["ejected"],
        "replica_states": stats["replica_states"],
        "recompiles": guard.total_recompiles,
        "host_transfers": guard.host_transfers,
    }
    router.close()
    return result


def run_spec_workload(model, args, cfg, max_length, rng, tracer=None):
    """The speculative A/B: a repetition-heavy workload (each prompt tiles a
    short motif — prompt-lookup's natural habitat, and greedy decode of small
    models collapses into loops anyway) served through two otherwise-identical
    engines, speculation OFF vs ON. The ON pass runs under an armed TraceGuard
    with the same hard 0-recompile / 0-host-transfer gate as the main timed
    passes, and reports accepted_tokens_per_step measured over the TIMED pass
    only — the speedup is a number in the artifact, not a claim."""
    from accelerate_tpu.analysis import TraceGuard
    from accelerate_tpu.serving import ContinuousBatcher

    def motif_prompt():
        motif = rng.integers(1, cfg.vocab_size, (6,)).astype(np.int32)
        length = int(rng.integers(args.prompt_min, max(args.prompt_min + 1, args.prompt_max // 2)))
        return np.tile(motif, -(-length // motif.size))[:length].astype(np.int32)

    prompts = [motif_prompt() for _ in range(args.requests)]
    # Decode-heavy on purpose: full budgets give greedy decode time to settle
    # into its loops, which is where prompt-lookup acceptance compounds.
    budgets = [args.max_new_max for _ in range(args.requests)]
    arrivals = np.cumsum(rng.exponential(args.mean_interarrival, size=args.requests))

    result = {"draft_tokens": args.draft_tokens, "draft_ngram": args.draft_ngram}
    for label, spec_on in (("plain", False), ("speculative", True)):
        engine = ContinuousBatcher(
            model, num_slots=args.num_slots, max_length=max_length,
            chunk_size=args.chunk_size,
            page_size=args.page_size, tracer=tracer, speculative=spec_on,
            draft_tokens=args.draft_tokens, draft_ngram=args.draft_ngram,
            max_queue=args.requests,
        )
        log(f"speculative workload ({label}): warmup...")
        # The closed bucket ladder, then twice through the real traffic (pass 1
        # registers prefixes, pass 2 runs the prefix-hit path) like the prefix
        # workload.
        engine.warm_inserts()
        run_continuous(engine, prompts, budgets, arrivals)
        run_continuous(engine, prompts, budgets, arrivals)
        registry = engine.metrics
        steps0 = registry.value("serving_spec_verify_steps_total") or 0
        accepted0 = registry.value("serving_spec_accepted_draft_tokens_total") or 0
        guard = TraceGuard(
            transfer_guard="disallow", on_violation="record",
            name=f"serving-bench-spec-{label}",
        )
        engine.trace_guard = guard
        with guard:
            tps, ttfts, iters, span = run_continuous(engine, prompts, budgets, arrivals)
        if guard.total_recompiles or guard.host_transfers:
            log(f"TRACE-GUARD VIOLATIONS in speculative workload ({label}): {guard.report().summary()}")
        # The speculation-overhead pin: the draft/verify chunk must hold the
        # same steady-state discipline as the plain one.
        assert guard.total_recompiles == 0 and guard.host_transfers == 0, (
            f"speculative workload ({label}) regressed the 0-recompile / "
            f"0-host-transfer discipline: {guard.report().summary()}"
        )
        block = {
            "tokens_per_sec": round(tps, 2),
            "ttft_p50_ms": round(pct(ttfts, 50) * 1000, 2),
            "ttft_p99_ms": round(pct(ttfts, 99) * 1000, 2),
            "makespan_s": round(span, 3),
            "decode_iterations": iters,
            "recompiles": guard.total_recompiles,
            "host_transfers": guard.host_transfers,
        }
        if spec_on:
            steps = (registry.value("serving_spec_verify_steps_total") or 0) - steps0
            accepted = (registry.value("serving_spec_accepted_draft_tokens_total") or 0) - accepted0
            block["verify_steps"] = int(steps)
            block["accepted_draft_tokens"] = int(accepted)
            block["accepted_tokens_per_step"] = (
                round((steps + accepted) / steps, 4) if steps else None
            )
            block["cumulative"] = engine.stats["speculative"]
        result[label] = block
    spec, plain = result["speculative"], result["plain"]
    result["accepted_tokens_per_step"] = spec["accepted_tokens_per_step"]
    result["decode_iterations_ratio_plain_over_spec"] = round(
        plain["decode_iterations"] / max(spec["decode_iterations"], 1), 3
    )
    return result


def estimate_decode_hbm_bytes(
    num_slots, pages_per_slot, page_size, model_cfg, pool_dtype_bytes,
    compute_dtype_bytes=None,
):
    """Estimated HBM bytes the attention CACHE READ moves per decode step,
    derived from pool geometry (worst case: every slot's full page window)
    and PER-PASS dtypes — `pool_dtype_bytes` from the live engine's pool
    leaves (`engine.kv_pool_itemsize`), never the params dtype, and
    `compute_dtype_bytes` for the buffers XLA materializes in the compute
    dtype. Per implementation:

      - ``xla``: `_live_page_attention` reads the live pool pages a block at
        a time (POOL dtype — the only quantized pass), writes each gathered
        block, then a reduction reads the block back — three passes over what
        is live, the whole window in this worst case; the estimate charges
        the write + re-read at COMPUTE dtype (the v5e's compiler fuses the
        dequantize into the reduction and keeps the block in fast memory:
        PERF.md §6, PR 28).
      - ``pallas_paged``: the kernel streams each table page into VMEM once —
        1 pass at POOL dtype, no materialized buffer.

    An estimate, not a measurement (XLA may fuse or spill differently): its
    job is to size the bandwidth claim a real-hardware run should verify."""
    if compute_dtype_bytes is None:
        compute_dtype_bytes = pool_dtype_bytes
    L = pages_per_slot * page_size
    hkv = getattr(model_cfg, "num_key_value_heads", model_cfg.num_attention_heads)
    values = num_slots * L * hkv * model_cfg.head_dim * 2  # K + V
    per_layer = {
        "xla": values * (pool_dtype_bytes + 2 * compute_dtype_bytes),
        "pallas_paged": values * pool_dtype_bytes,
    }
    return {
        impl: val * model_cfg.num_hidden_layers for impl, val in per_layer.items()
    }


def run_attention_workload(model, args, cfg, max_length, workload, tracer=None):
    """The kernel-vs-XLA A/B: the SAME mixed workload served through two
    otherwise-identical paged engines, attention_impl "xla" (the live-page oracle)
    vs "pallas_paged" (fused page-walk kernels). Each engine's timed pass
    runs under an armed TraceGuard with the hard 0-recompile /
    0-host-transfer gate — the kernel path must hold the compiled-once
    discipline, not just match tokens — and the block records the impl each
    decode executable ACTUALLY traced (`ops.attention.LAST_DISPATCH`), the
    decode tokens/sec, the mean per-dispatch / per-decode-step chunk seconds,
    and the pool-geometry HBM estimate, so the MFU/bandwidth claim is a
    recorded artifact for the next real-hardware run."""
    from accelerate_tpu.analysis import TraceGuard
    from accelerate_tpu.ops import attention as attention_ops
    from accelerate_tpu.serving import ContinuousBatcher

    import jax

    prompts, budgets, arrivals = workload
    # Off-TPU, pallas_paged runs the Pallas INTERPRETER (the CPU-test shim):
    # parity and the 0-recompile discipline are real, the timing is not — the
    # block records it so a CPU-smoke ratio can never pass as TPU behavior.
    interpreted = jax.default_backend() != "tpu"
    if interpreted:
        log(
            "attention A/B off-TPU: pallas_paged runs the Pallas interpreter — "
            "parity/discipline are meaningful, tokens/sec ratios are NOT "
            "(interpreted=true is recorded in the block)"
        )
    result = {"backend": jax.default_backend()}
    for impl in ("xla", "pallas_paged"):
        engine = ContinuousBatcher(
            model, num_slots=args.num_slots, max_length=max_length,
            chunk_size=args.chunk_size, page_size=args.page_size,
            tracer=tracer, max_queue=args.requests, attention_impl=impl,
            weight_dtype=args.weight_dtype, kv_cache_dtype=args.kv_cache_dtype,
        )
        # Honest dtype accounting: pool passes at the LIVE pool leaf dtype
        # (int8/fp8 pools move 1 byte/value), XLA's materialized gather at
        # the compute dtype — never a single params-derived figure.
        pool_bytes = engine.kv_pool_itemsize
        compute_bytes = np.dtype(
            jax.tree_util.tree_leaves(model.params)[0].dtype
        ).itemsize
        log(f"attention workload ({impl}): warmup...")
        engine.warm_inserts()
        run_continuous(engine, prompts, budgets, arrivals)
        # The chunk executable traced during the pass above; LAST_DISPATCH is
        # a trace-time record, so it still names the impl that program chose.
        dispatch_impl = attention_ops.LAST_DISPATCH
        run_continuous(engine, prompts, budgets, arrivals)
        registry = engine.metrics
        chunk_hist = registry.get("serving_chunk_seconds")
        count0, sum0 = chunk_hist.count, chunk_hist.sum
        guard = TraceGuard(
            transfer_guard="disallow", on_violation="record",
            name=f"serving-bench-attention-{impl}",
        )
        engine.trace_guard = guard
        with guard:
            tps, ttfts, iters, span = run_continuous(engine, prompts, budgets, arrivals)
        if guard.total_recompiles or guard.host_transfers:
            log(f"TRACE-GUARD VIOLATIONS in attention workload ({impl}): {guard.report().summary()}")
        # The kernel-path discipline pin: pallas_paged must hold the same
        # steady state as the oracle — one decode executable, page tables as
        # traced operands, zero host syncs.
        assert guard.total_recompiles == 0 and guard.host_transfers == 0, (
            f"attention workload ({impl}) regressed the 0-recompile / "
            f"0-host-transfer discipline: {guard.report().summary()}"
        )
        chunks = chunk_hist.count - count0
        chunk_s = (chunk_hist.sum - sum0) / max(chunks, 1)
        hbm = estimate_decode_hbm_bytes(
            args.num_slots, engine.pages_per_slot, args.page_size, cfg,
            pool_bytes, compute_bytes,
        )
        result[impl] = {
            "dispatch_impl": dispatch_impl,
            "interpreted": interpreted and impl == "pallas_paged",
            "tokens_per_sec": round(tps, 2),
            "decode_iterations": iters,
            "ttft_p50_ms": round(pct(ttfts, 50) * 1000, 2),
            "ttft_p99_ms": round(pct(ttfts, 99) * 1000, 2),
            "makespan_s": round(span, 3),
            "decode_chunk_mean_s": round(chunk_s, 6),
            "decode_attention_s_per_dispatch": round(chunk_s / args.chunk_size, 6),
            "est_hbm_bytes_per_decode_step": hbm[impl],
            "recompiles": guard.total_recompiles,
            "host_transfers": guard.host_transfers,
        }
    result["tokens_per_sec_ratio_pallas_over_xla"] = round(
        result["pallas_paged"]["tokens_per_sec"] / max(result["xla"]["tokens_per_sec"], 1e-9), 3
    )
    result["est_hbm_bytes_ratio_xla_over_pallas"] = round(
        result["xla"]["est_hbm_bytes_per_decode_step"]
        / max(result["pallas_paged"]["est_hbm_bytes_per_decode_step"], 1), 3
    )
    return result


def run_quant_workload(model, args, cfg, max_length, workload, tracer=None):
    """The quantization A/B: the SAME mixed workload served through
    otherwise-identical paged engines — bf16 baseline, int8 weights + int8 KV
    pool, int8 weights + fp8_e4m3 KV pool — each timed pass under the hard
    0-recompile / 0-host-transfer gate (dtypes are static config, scales are
    traced operands: quantization must not cost the compiled-once
    discipline). Per row the block records decode tokens/sec, per-dispatch
    attention seconds, the ACTUAL pool bytes (`engine.kv_cache_nbytes`,
    scales included) and weight bytes, the pool-geometry HBM estimate off the
    live pool dtype, token agreement against the bf16 row's streams, the max
    logit error of the quantized-weight forward vs dense on a probe batch,
    and interpreter provenance. Asserts the headline acceptance number: int8
    KV cuts estimated cache-read bytes >= 2x vs bf16 at identical geometry."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.analysis import TraceGuard
    from accelerate_tpu.ops.quantization import params_nbytes, quantize_params_int8, weight_autocast
    from accelerate_tpu.serving import ContinuousBatcher

    prompts, budgets, arrivals = workload
    interpreted = (
        args.attention_impl == "pallas_paged" and jax.default_backend() != "tpu"
    )

    # Max logit error of the int8-weight forward vs dense, one probe batch —
    # the weight-quantization accuracy budget as a recorded artifact. Probe
    # width is the shortest sampled prompt, so ragged --prompt-min/-max
    # settings below 8 tokens still stack.
    width = min(8, min(p.size for p in prompts[:4]))
    probe = jnp.asarray(np.stack([p[:width] for p in prompts[:4]]).astype(np.int32))
    dense_logits = np.asarray(model.apply_fn(model.params, probe), np.float32)
    qparams = quantize_params_int8(
        model.params if "params" in model.params else {"params": model.params}
    )
    with weight_autocast("int8"):
        int8_logits = np.asarray(jax.jit(model.apply_fn)(qparams, probe), np.float32)
    weight_max_logit_err = float(np.abs(int8_logits - dense_logits).max())

    rows = (
        ("bf16", "bf16", "bf16"),
        ("int8", "int8", "int8"),
        ("fp8_e4m3", "int8", "fp8_e4m3"),
    )
    result = {
        "backend": jax.default_backend(),
        "attention_impl": args.attention_impl,
        "weight_int8_max_logit_error_vs_bf16": round(weight_max_logit_err, 6),
    }
    baseline_tokens = None
    for label, weight_dtype, kv_dtype in rows:
        engine = ContinuousBatcher(
            model, num_slots=args.num_slots, max_length=max_length,
            chunk_size=args.chunk_size, page_size=args.page_size,
            tracer=tracer, max_queue=args.requests,
            attention_impl=args.attention_impl,
            weight_dtype=weight_dtype, kv_cache_dtype=kv_dtype,
        )
        log(f"quantization workload ({label}): warmup...")
        engine.warm_inserts()
        run_continuous(engine, prompts, budgets, arrivals)
        run_continuous(engine, prompts, budgets, arrivals)
        registry = engine.metrics
        chunk_hist = registry.get("serving_chunk_seconds")
        count0, sum0 = chunk_hist.count, chunk_hist.sum
        guard = TraceGuard(
            transfer_guard="disallow", on_violation="record",
            name=f"serving-bench-quant-{label}",
        )
        engine.trace_guard = guard
        tokens = {}
        with guard:
            tps, ttfts, iters, span = run_continuous(
                engine, prompts, budgets, arrivals, collect_tokens=tokens
            )
        if guard.total_recompiles or guard.host_transfers:
            log(f"TRACE-GUARD VIOLATIONS in quantization workload ({label}): {guard.report().summary()}")
        # The quantization-discipline pin: static dtypes + traced scale
        # operands must keep the one-executable / zero-sync steady state.
        assert guard.total_recompiles == 0 and guard.host_transfers == 0, (
            f"quantization workload ({label}) regressed the 0-recompile / "
            f"0-host-transfer discipline: {guard.report().summary()}"
        )
        if baseline_tokens is None:
            baseline_tokens = tokens
            agreement = 1.0
        else:
            pairs = [
                (x, y)
                for i in baseline_tokens
                for x, y in zip(baseline_tokens[i], tokens.get(i, []))
            ]
            agreement = (
                sum(x == y for x, y in pairs) / len(pairs) if pairs else None
            )
        chunks = chunk_hist.count - count0
        chunk_s = (chunk_hist.sum - sum0) / max(chunks, 1)
        compute_bytes = np.dtype(
            jax.tree_util.tree_leaves(model.params)[0].dtype
        ).itemsize
        hbm = estimate_decode_hbm_bytes(
            args.num_slots, engine.pages_per_slot, args.page_size, cfg,
            engine.kv_pool_itemsize, compute_bytes,
        )
        result[label] = {
            "weight_dtype": weight_dtype,
            "kv_cache_dtype": kv_dtype,
            "interpreted": interpreted,
            "tokens_per_sec": round(tps, 2),
            "ttft_p50_ms": round(pct(ttfts, 50) * 1000, 2),
            "ttft_p99_ms": round(pct(ttfts, 99) * 1000, 2),
            "makespan_s": round(span, 3),
            "decode_iterations": iters,
            "decode_chunk_mean_s": round(chunk_s, 6),
            "decode_attention_s_per_dispatch": round(chunk_s / args.chunk_size, 6),
            "kv_pool_bytes": engine.kv_cache_nbytes,
            "kv_pool_itemsize": engine.kv_pool_itemsize,
            "weight_bytes": params_nbytes(engine.params),
            # Both impls' estimates ride every row: the serving impl's number
            # is what THIS engine moved; the pallas one is the fused-dequant
            # hot-path claim (the XLA oracle re-materializes the gather in
            # the compute dtype, so its quantized saving is structurally
            # smaller — that is the point of fusing).
            "est_hbm_bytes_per_decode_step": hbm[args.attention_impl],
            "est_hbm_bytes_per_decode_step_pallas": hbm["pallas_paged"],
            "token_agreement_vs_bf16": round(agreement, 4) if agreement is not None else None,
            "recompiles": guard.total_recompiles,
            "host_transfers": guard.host_transfers,
        }
    ratio = result["bf16"]["est_hbm_bytes_per_decode_step_pallas"] / max(
        result["int8"]["est_hbm_bytes_per_decode_step_pallas"], 1
    )
    result["est_cache_hbm_ratio_bf16_over_int8"] = round(ratio, 3)
    # The acceptance headline, evaluated on the fused-kernel path (one pool
    # pass — where the pool dtype IS the traffic): int8 KV at identical pool
    # geometry must at least halve the estimated cache-read bytes per step.
    assert ratio >= 2.0, (
        f"int8 KV cache only cut estimated cache-read HBM bytes by {ratio:.2f}x "
        "(expected >= 2x at identical pool geometry) — dtype accounting is off"
    )
    result["kv_pool_bytes_ratio_bf16_over_int8"] = round(
        result["bf16"]["kv_pool_bytes"] / max(result["int8"]["kv_pool_bytes"], 1), 3
    )
    result["weight_bytes_ratio_bf16_over_int8"] = round(
        result["bf16"]["weight_bytes"] / max(result["int8"]["weight_bytes"], 1), 3
    )
    return result


def _run_guarded_engine_pass(model, args, cfg, max_length, workload, tracer, label, **engine_kwargs):
    """One engine through the shared A/B measurement harness: build it, warm
    the insert ladder, run the workload twice unguarded (compiles + page-pool
    steady state), then once under an armed TraceGuard collecting tokens.
    Returns (row, tokens, engine) — `row` carries the timing/footprint fields
    every A/B block shares, with the 0-recompile / 0-host-transfer gate
    already asserted."""
    from accelerate_tpu.analysis import TraceGuard
    from accelerate_tpu.serving import ContinuousBatcher

    prompts, budgets, arrivals = workload
    engine = ContinuousBatcher(
        model, num_slots=args.num_slots, max_length=max_length,
        chunk_size=args.chunk_size,
        page_size=args.page_size, tracer=tracer, max_queue=args.requests,
        attention_impl=args.attention_impl,
        weight_dtype=args.weight_dtype, kv_cache_dtype=args.kv_cache_dtype,
        **engine_kwargs,
    )
    log(f"{label}: warmup...")
    engine.warm_inserts()
    run_continuous(engine, prompts, budgets, arrivals)
    run_continuous(engine, prompts, budgets, arrivals)
    chunk_hist = engine.metrics.get("serving_chunk_seconds")
    count0, sum0 = chunk_hist.count, chunk_hist.sum
    guard = TraceGuard(
        transfer_guard="disallow", on_violation="record", name=f"serving-bench-{label}",
    )
    engine.trace_guard = guard
    tokens = {}
    with guard:
        tps, ttfts, iters, span = run_continuous(
            engine, prompts, budgets, arrivals, collect_tokens=tokens
        )
    if guard.total_recompiles or guard.host_transfers:
        log(f"TRACE-GUARD VIOLATIONS in {label}: {guard.report().summary()}")
    # The sharded-operand discipline pin: collectives inserted by GSPMD
    # must not cost the one-executable / zero-host-sync steady state.
    assert guard.total_recompiles == 0 and guard.host_transfers == 0, (
        f"{label} regressed the 0-recompile / 0-host-transfer discipline: "
        f"{guard.report().summary()}"
    )
    chunks = chunk_hist.count - count0
    chunk_s = (chunk_hist.sum - sum0) / max(chunks, 1)
    row = {
        "tokens_per_sec": round(tps, 2),
        "ttft_p50_ms": round(pct(ttfts, 50) * 1000, 2),
        "ttft_p99_ms": round(pct(ttfts, 99) * 1000, 2),
        "makespan_s": round(span, 3),
        "decode_iterations": iters,
        "decode_chunk_mean_s": round(chunk_s, 6),
        "per_chip_weight_bytes": engine.per_device_weight_nbytes,
        "per_chip_kv_pool_bytes": engine.per_device_kv_cache_nbytes,
        "params_leaves_sharded": sum(
            1 for spec in engine.tp_sharding_report()["params"].values() if "model" in spec
        ),
        "recompiles": guard.total_recompiles,
        "host_transfers": guard.host_transfers,
    }
    return row, tokens, engine


def _token_agreement(baseline_tokens, tokens, what):
    """Exact greedy-token agreement between two passes of the same workload:
    identical per-request token COUNTS (a zip would silently forgive a short
    stream) and identical values. GSPMD partitioning is a layout change, not
    a numerics change, so anything under 1.0 asserts."""
    lengths = {i: len(v) for i, v in baseline_tokens.items()}
    assert lengths == {i: len(v) for i, v in tokens.items()}, (
        f"{what} emitted a different token COUNT per request"
    )
    pairs = [
        (x, y)
        for i in baseline_tokens
        for x, y in zip(baseline_tokens[i], tokens.get(i, []))
    ]
    agreement = sum(x == y for x, y in pairs) / len(pairs) if pairs else None
    assert agreement == 1.0, (
        f"{what} diverged (agreement {agreement}) — sharded decode is not token-exact"
    )
    return agreement


def run_tensor_parallel_workload(model, args, cfg, max_length, workload, tracer=None):
    """The tensor-parallel A/B (`--tp N`): the SAME mixed workload served by a
    single-device engine and by one engine spanning an N-device submesh
    (weights Megatron-sharded by the model family's rules, the KV pool
    sharded by KV head, page tables and sampling scalars replicated traced
    operands). Per row the block records decode tokens/sec, per-dispatch
    attention seconds, and PER-CHIP weight + KV-pool bytes read off the live
    shardings (`engine.per_device_*_nbytes`), each timed pass under the hard
    0-recompile / 0-host-transfer gate. Asserts the two acceptance headlines:
    greedy token IDENTITY tp=N vs tp=1, and combined per-chip weight+pool
    bytes dropping to ~1/N (>= 60% of the ideal reduction — replicated
    norms/biases/scalars keep it off the exact bound)."""
    import jax

    tp_n = int(args.tp)
    result = {
        "backend": jax.default_backend(),
        "attention_impl": args.attention_impl,
        "kv_cache_dtype": args.kv_cache_dtype,
        "weight_dtype": args.weight_dtype,
        "devices_visible": len(jax.devices()),
    }
    baseline_tokens = None
    for tp in (1, tp_n):
        label = f"tp{tp}"
        row, tokens, engine = _run_guarded_engine_pass(
            model, args, cfg, max_length, workload, tracer,
            f"tensor-parallel workload ({label})",
            tp=tp, sharding_rules=getattr(args, "sharding", None),
        )
        if baseline_tokens is None:
            baseline_tokens = tokens
            agreement = 1.0
        else:
            agreement = _token_agreement(
                baseline_tokens, tokens, f"tp={tp} vs tp=1 greedy tokens"
            )
        row["tp"] = tp
        row["decode_attention_s_per_dispatch"] = round(
            row["decode_chunk_mean_s"] / args.chunk_size, 6
        )
        row["token_agreement_vs_tp1"] = round(agreement, 4) if agreement is not None else None
        result[label] = row
    base = result["tp1"]["per_chip_weight_bytes"] + result["tp1"]["per_chip_kv_pool_bytes"]
    tp_key = f"tp{tp_n}"
    spanned = result[tp_key]["per_chip_weight_bytes"] + result[tp_key]["per_chip_kv_pool_bytes"]
    ratio = base / max(spanned, 1)
    result["per_chip_bytes_ratio_tp1_over_tpN"] = round(ratio, 3)
    result["tokens_per_sec_ratio_tpN_over_tp1"] = round(
        result[tp_key]["tokens_per_sec"] / max(result["tp1"]["tokens_per_sec"], 1e-9), 3
    )
    # The footprint headline: per-chip weight+pool bytes must approach 1/N.
    # 60% of ideal leaves room for replicated norms/biases/pad masks at the
    # tiny CPU-smoke sizes; real model shapes sit much closer to N.
    assert ratio >= 1.0 + 0.6 * (tp_n - 1), (
        f"tp={tp_n} only cut per-chip weight+pool bytes {ratio:.2f}x "
        f"(expected >= {1.0 + 0.6 * (tp_n - 1):.2f}x) — something is "
        "silently replicated (see engine.tp_sharding_report())"
    )
    return result


def run_sharding_plan_workload(model, args, cfg, max_length, workload, tracer=None):
    """The sharding-source A/B (`--tp N` engines, hand `rules` vs planner
    `auto`): the SAME mixed workload served by two mesh-spanning engines that
    differ ONLY in where their partition table came from — the model family's
    hand-written rules, or the cost-model planner's emitted table
    (`parallel/planner.py`, `sharding_rules="auto"`). Per row: decode
    tokens/sec, per-chip weight + KV-pool bytes read off the LIVE shardings,
    and for the auto engine the planner's predictions next to reality — the
    predicted-vs-live per-chip byte error and the predicted-vs-measured
    step-time error (the honesty metric behind measure-and-refine). Asserts
    the acceptance headlines: greedy tokens IDENTICAL auto vs rules, both
    engines under the 0-recompile / 0-host-transfer gate, and auto per-chip
    weight+pool bytes at >= 60% of the ideal 1/N reduction off the
    replicated footprint."""
    import jax

    tp_n = int(args.tp)
    result = {
        "backend": jax.default_backend(),
        "tp": tp_n,
        "devices_visible": len(jax.devices()),
    }
    baseline_tokens = None
    for mode in ("rules", "auto"):
        row, tokens, engine = _run_guarded_engine_pass(
            model, args, cfg, max_length, workload, tracer,
            f"sharding-plan workload ({mode})",
            tp=tp_n, sharding_rules=mode,
        )
        if baseline_tokens is None:
            baseline_tokens = tokens
            agreement = 1.0
        else:
            # The planner emits a table the SAME GSPMD derivation consumes:
            # a layout change, never a numerics change.
            agreement = _token_agreement(
                baseline_tokens, tokens, "sharding_rules='auto' vs the hand rules"
            )
        measured_step_s = row["decode_chunk_mean_s"] / args.chunk_size
        row["sharding"] = mode
        row["measured_step_s"] = round(measured_step_s, 6)
        row["token_agreement_vs_rules"] = round(agreement, 4) if agreement is not None else None
        if engine.sharding_plan is not None:
            plan = engine.sharding_plan
            predicted_bytes = plan.cost.per_chip_param_bytes
            live_bytes = engine.per_device_weight_nbytes
            predicted_step = plan.cost.step_time_s
            row["planner"] = {
                "rules_emitted": len(plan.rules),
                "predicted_per_chip_param_bytes": int(predicted_bytes),
                "predicted_per_chip_kv_bytes": int(plan.cost.per_chip_kv_bytes),
                "predicted_collective_bytes_per_dispatch": int(plan.cost.collective_bytes),
                "predicted_step_s": round(predicted_step, 9),
                "predicted_vs_live_bytes_error": round(
                    abs(predicted_bytes - live_bytes) / max(live_bytes, 1), 4
                ),
                "predicted_vs_measured_step_error": round(
                    abs(predicted_step - measured_step_s) / max(measured_step_s, 1e-12), 4
                ),
            }
        # The footprint headline off the LIVE shardings: per-chip weight+pool
        # bytes at >= 60% of the ideal 1/N cut from the replicated footprint
        # (replicated norms/biases/page tables keep it off the exact bound).
        replicated = sum(
            int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
            for tree in (engine.params, engine._cache)
            for leaf in jax.tree_util.tree_leaves(tree)
        )
        spanned = row["per_chip_weight_bytes"] + row["per_chip_kv_pool_bytes"]
        ratio = replicated / max(spanned, 1)
        row["per_chip_bytes_ratio_vs_replicated"] = round(ratio, 3)
        assert ratio >= 1.0 + 0.6 * (tp_n - 1), (
            f"sharding={mode} only cut per-chip weight+pool bytes {ratio:.2f}x "
            f"(expected >= {1.0 + 0.6 * (tp_n - 1):.2f}x) — something is "
            "silently replicated (see engine.tp_sharding_report())"
        )
        result[mode] = row
    result["tokens_per_sec_ratio_auto_over_rules"] = round(
        result["auto"]["tokens_per_sec"] / max(result["rules"]["tokens_per_sec"], 1e-9), 3
    )
    return result


def run_prefix_workload(model, args, cfg, max_length, rng, tracer=None):
    """The prefix-heavy serving workload: every request opens with the SAME
    `--prefix-tokens`-long system prompt followed by a random tail. Served
    twice through paged engines — shared-prefix cache ON vs OFF — so the
    prefill-tokens-saved and TTFT deltas are measured against a same-run
    baseline, with a fresh TraceGuard armed over each timed pass (the paged
    cache must hold the 0-recompile / 0-host-transfer discipline too)."""
    from accelerate_tpu.analysis import TraceGuard
    from accelerate_tpu.serving import ContinuousBatcher

    prefix = rng.integers(1, cfg.vocab_size, (args.prefix_tokens,)).astype(np.int32)
    tail_max = max(args.prompt_min, max_length - args.max_new_max - args.prefix_tokens)
    prompts = [
        np.concatenate(
            [prefix, rng.integers(1, cfg.vocab_size, (int(rng.integers(args.prompt_min, tail_max + 1)),)).astype(np.int32)]
        )
        for _ in range(args.requests)
    ]
    budgets = [int(rng.integers(args.max_new_min, args.max_new_max + 1)) for _ in range(args.requests)]
    arrivals = np.cumsum(rng.exponential(args.mean_interarrival, size=args.requests))

    result = {"prefix_tokens": args.prefix_tokens}
    for label, use_prefix in (("uncached", False), ("cached", True)):
        engine = ContinuousBatcher(
            model, num_slots=args.num_slots, max_length=max_length,
            chunk_size=args.chunk_size, page_size=args.page_size,
            prefix_cache=use_prefix, tracer=tracer, max_queue=args.requests,
        )
        log(f"prefix workload ({label}): warmup...")
        # The closed bucket ladder first (no admission can mint a fresh
        # bucket), then twice through the real traffic: pass 1 registers the
        # prefix, pass 2 runs the prefix-HIT suffix path before timing.
        engine.warm_inserts()
        run_continuous(engine, prompts, budgets, arrivals)
        run_continuous(engine, prompts, budgets, arrivals)
        guard = TraceGuard(
            transfer_guard="disallow", on_violation="record",
            name=f"serving-bench-prefix-{label}",
        )
        engine.trace_guard = guard
        with guard:
            tps, ttfts, _iters, span = run_continuous(engine, prompts, budgets, arrivals)
        if guard.total_recompiles or guard.host_transfers:
            log(f"TRACE-GUARD VIOLATIONS in prefix workload ({label}): {guard.report().summary()}")
        # The tracing-overhead pin, prefix half: span instrumentation rides
        # these timed passes too and must not cost a recompile or a sync.
        assert guard.total_recompiles == 0 and guard.host_transfers == 0, (
            f"prefix workload ({label}) regressed the 0-recompile / 0-host-transfer "
            f"discipline with tracing enabled: {guard.report().summary()}"
        )
        stats = engine.stats
        result[label] = {
            "tokens_per_sec": round(tps, 2),
            "ttft_p50_ms": round(pct(ttfts, 50) * 1000, 2),
            "ttft_p99_ms": round(pct(ttfts, 99) * 1000, 2),
            "makespan_s": round(span, 3),
            "prefill_tokens_saved": stats["prefix_cache"]["prefill_tokens_saved"],
            "prefix_hits": stats["prefix_cache"]["hits"],
            "prefix_misses": stats["prefix_cache"]["misses"],
            "prefix_evictions": stats["prefix_cache"]["evictions"],
            "pages_total": stats["pages_total"],
            "recompiles": guard.total_recompiles,
            "host_transfers": guard.host_transfers,
        }
    result["ttft_p50_ratio_uncached_over_cached"] = round(
        result["uncached"]["ttft_p50_ms"] / max(result["cached"]["ttft_p50_ms"], 1e-9), 3
    )
    return result


def run_ramp_workload(model, args, cfg, max_length, rng, tracer=None):
    """The open-loop capacity ramp (`--workload ramp`): requests arrive at a
    FIXED offered rate regardless of completions (open loop — the arrival
    process never slows down for a saturated server, unlike the closed-loop
    workloads above), swept over geometrically increasing rates. Each level
    records p99 TTFT against offered load; the **knee point** — the highest
    offered rate whose p99 TTFT stays within `--ramp-knee-factor` of the
    unloaded level — is the fleet's capacity number, emitted in the JSON.

    Runs against the in-process fleet by default and against REAL subprocess
    engine workers with `--out-of-process`: same workload, same knee
    definition, so the two topologies' capacity numbers are comparable. The
    0-recompile / 0-host-transfer discipline is enforced per engine — a
    process-wide TraceGuard in-process, the workers' own guards (reset after
    warmup, read back through stats) out of process."""
    from accelerate_tpu.analysis import TraceGuard
    from accelerate_tpu.router import Router
    from accelerate_tpu.serving import QueueFull, Request

    n = args.ramp_requests
    prompts = [
        rng.integers(1, cfg.vocab_size, (int(rng.integers(args.prompt_min, args.prompt_max + 1)),)).astype(np.int32)
        for _ in range(n)
    ]
    budgets = [int(rng.integers(args.max_new_min, args.max_new_max + 1)) for _ in range(n)]
    replicas = max(args.replicas, 1)
    router = Router(
        model,
        replicas=replicas,
        num_slots=args.num_slots,
        max_length=max_length,
        chunk_size=args.chunk_size,
        # Open loop: overload must surface as TTFT blow-up (the knee), not as
        # rejected arrivals — the queue bound is sized above one full level.
        max_queue=max(4 * n, 64),
        default_deadline_s=600.0,
        page_size=args.page_size,
        tracer=tracer,
        out_of_process=args.out_of_process,
        worker_kwargs=(
            dict(guard=True, transport=args.transport) if args.out_of_process else None
        ),
        stall_degrade_s=None,
        weight_dtype=args.weight_dtype, kv_cache_dtype=args.kv_cache_dtype,
    )
    next_id = 0

    def run_level(rate):
        """One offered-load level on the shared virtual clock (real step
        durations, virtual arrivals at `rate` req/s). Returns per-request
        TTFTs and the rejected count."""
        nonlocal next_id
        base = next_id
        arrivals = {base + i: i / rate for i in range(n)}
        clock = 0.0
        submitted = 0
        rejected = 0
        first_seen = {}
        while submitted < n or router.pending:
            while submitted < n and arrivals[base + submitted] <= clock:
                rid = base + submitted
                try:
                    router.submit(Request(
                        rid, prompts[submitted], max_new_tokens=budgets[submitted]
                    ))
                except QueueFull:
                    rejected += 1
                submitted += 1
            if not router.pending and submitted < n:
                clock = max(clock, arrivals[base + submitted])
                continue
            t0 = time.perf_counter()
            events = router.step()
            clock += time.perf_counter() - t0
            for rid, _toks in events:
                first_seen.setdefault(rid, clock)
        next_id = base + n
        ttfts = [first_seen[rid] - arrivals[rid] for rid in sorted(first_seen)]
        for rid in list(router.results):
            router.release(rid)
        return ttfts, rejected

    rates = [args.ramp_base_rate * (2.0 ** k) for k in range(args.ramp_levels)]
    log(f"ramp workload ({'out-of-process' if args.out_of_process else 'in-process'}, "
        f"{replicas} replica(s)): warmup...")
    warmed = router.warm_inserts()
    log(f"ramp insert buckets warmed: {sorted(set(sum(warmed.values(), [])))}")
    run_level(rates[0])  # decode executables + prefix floors warm

    guard = None
    if args.out_of_process:
        for replica in router.replica_set.replicas:
            assert replica.engine.reset_guard(), "worker spawned without --guard"
    else:
        guard = TraceGuard(
            transfer_guard="disallow", on_violation="record", name="serving-bench-ramp"
        )
        guard.__enter__()

    levels = []
    for rate in rates:
        ttfts, rejected = run_level(rate)
        completed = len(ttfts)
        levels.append({
            "offered_rps": round(rate, 3),
            "offered_tokens_per_sec": round(rate * float(np.mean(budgets)), 2),
            "completed": completed,
            "rejected": rejected,
            "ttft_p50_ms": round(pct(ttfts, 50) * 1000, 2) if ttfts else None,
            "ttft_p99_ms": round(pct(ttfts, 99) * 1000, 2) if ttfts else None,
        })
        log(f"ramp level {rate:.1f} req/s: p99 TTFT {levels[-1]['ttft_p99_ms']}ms, "
            f"{completed}/{n} completed, {rejected} rejected")

    worker_guards = None
    if guard is not None:
        guard.__exit__(None, None, None)
        assert guard.total_recompiles == 0 and guard.host_transfers == 0, (
            "ramp workload regressed the 0-recompile / 0-host-transfer discipline: "
            f"{guard.report().summary()}"
        )
        recompiles, host_transfers = guard.total_recompiles, guard.host_transfers
    else:
        # Per-worker discipline: every subprocess engine's own guard must have
        # stayed at zero across every timed level.
        worker_guards = {}
        recompiles = host_transfers = 0
        for replica in router.replica_set.replicas:
            stats = replica.engine.stats
            info = (stats.get("worker") or {}).get("guard") or {}
            worker_guards[replica.index] = info
            recompiles += int(info.get("recompiles", 0))
            host_transfers += int(info.get("host_transfers", 0))
        assert recompiles == 0 and host_transfers == 0, (
            "a subprocess worker regressed the 0-recompile / 0-host-transfer "
            f"discipline under the ramp: {worker_guards}"
        )

    # The knee: the highest offered rate whose p99 TTFT is still within
    # ramp_knee_factor of the unloaded (first) level — the capacity number.
    base_p99 = levels[0]["ttft_p99_ms"] or 1e-9
    knee = levels[0]
    for level in levels:
        if level["ttft_p99_ms"] is not None and (
            level["ttft_p99_ms"] <= args.ramp_knee_factor * base_p99
        ) and level["rejected"] == 0:
            knee = level
    saturated = knee is not levels[-1]
    router.close()
    return {
        "out_of_process": args.out_of_process,
        "transport": args.transport if args.out_of_process else None,
        "replicas": replicas,
        "requests_per_level": n,
        "levels": levels,
        "knee": {
            "offered_rps": knee["offered_rps"],
            "offered_tokens_per_sec": knee["offered_tokens_per_sec"],
            "ttft_p99_ms": knee["ttft_p99_ms"],
            "knee_factor": args.ramp_knee_factor,
            # False means every level stayed under the knee: the ramp never
            # reached saturation and capacity is a lower bound.
            "saturated": saturated,
        },
        "recompiles": recompiles,
        "host_transfers": host_transfers,
        "worker_guards": worker_guards,
    }


def run_transport_workload(model, args, cfg, max_length, rng, tracer=None):
    """The pipe-vs-socket transport A/B (loopback): the SAME mixed workload
    served through two out-of-process fleets of real subprocess workers
    (`accelerate_tpu.worker`) — one over the spawned stdio pipe framing, one
    over a loopback TCP socket (the worker self-listens, the controller dials
    and handshakes) — so the JSON records what the socket hop itself costs.
    Both fleets report tokens/sec, TTFT p50/p99, and the frame RTT histogram
    (`transport_rtt_seconds`, observed on every protocol roundtrip through
    the shared registry the router attaches); the delta between the two RTT
    medians is the wire overhead number. The framing is byte-identical on
    both transports, so greedy token parity across them is asserted, and BOTH
    paths hold the per-worker 0-recompile / 0-host-transfer discipline (each
    worker's own TraceGuard, reset after warmup, read back through stats)."""
    from accelerate_tpu.router import Router
    from accelerate_tpu.serving import Request

    prompts, budgets, arrivals = build_workload(args, cfg.vocab_size, rng)
    n = len(prompts)

    def run_fleet(transport):
        router = Router(
            model, replicas=1, num_slots=args.num_slots, max_length=max_length,
            chunk_size=args.chunk_size, max_queue=args.requests + 16,
            default_deadline_s=600.0,
            page_size=args.page_size, tracer=tracer, stall_degrade_s=None,
            weight_dtype=args.weight_dtype, kv_cache_dtype=args.kv_cache_dtype,
            out_of_process=True,
            worker_kwargs=dict(guard=True, transport=transport),
        )
        try:
            def run_traffic():
                clock = 0.0
                submitted = 0
                first_seen = {}
                delivered = 0
                while submitted < n or router.pending:
                    while submitted < n and float(arrivals[submitted]) <= clock:
                        router.submit(Request(
                            submitted, prompts[submitted],
                            max_new_tokens=budgets[submitted],
                        ))
                        submitted += 1
                    if not router.pending and submitted < n:
                        clock = float(arrivals[submitted])
                        continue
                    t0 = time.perf_counter()
                    events = router.step()
                    clock += time.perf_counter() - t0
                    for rid, toks in events:
                        first_seen.setdefault(rid, clock)
                        delivered += len(toks)
                tokens = {i: list(router.results[i].tokens) for i in range(n)}
                reasons = {}
                for i in range(n):
                    reason = router.results[i].finish_reason
                    reasons[reason] = reasons.get(reason, 0) + 1
                ttfts = [first_seen.get(i, clock) - float(arrivals[i]) for i in range(n)]
                makespan = clock - float(arrivals[0])
                for i in range(n):
                    router.release(i)
                return tokens, ttfts, delivered, makespan, reasons

            log(f"transport A/B ({transport}): warmup...")
            warmed = router.warm_inserts()
            log(f"transport A/B ({transport}) insert buckets warmed: "
                f"{sorted(set(sum(warmed.values(), [])))}")
            # Two warm passes, like the headline continuous path: the first
            # registers prompt prefixes, the second runs the prefix-HIT suffix
            # path, so the timed pass below can't mint a fresh executable.
            run_traffic()
            run_traffic()
            for replica in router.replica_set.replicas:
                assert replica.engine.reset_guard(), "worker spawned without --guard"
            tokens, ttfts, delivered, makespan, reasons = run_traffic()
            # Per-worker discipline: the transport must be a wire change, not
            # a compute change — the worker's own guard stayed at 0/0 across
            # the timed pass on BOTH transports (the ISSUE gate names the
            # socket path; holding pipe to the same bar keeps the A/B honest).
            worker_guards = {}
            recompiles = host_transfers = 0
            for replica in router.replica_set.replicas:
                stats = replica.engine.stats
                info = (stats.get("worker") or {}).get("guard") or {}
                worker_guards[replica.index] = info
                recompiles += int(info.get("recompiles", 0))
                host_transfers += int(info.get("host_transfers", 0))
            assert recompiles == 0 and host_transfers == 0, (
                f"a subprocess worker regressed the 0-recompile / "
                f"0-host-transfer discipline on the {transport} transport: "
                f"{worker_guards}"
            )
            # Frame RTT: every controller->worker protocol call observes its
            # roundtrip into the fleet registry (cumulative over warmup + the
            # timed pass — the transport's wire cost, not workload timing).
            rtt = router.metrics.get("transport_rtt_seconds", {"replica": "0"})
            rtt_block = None
            if rtt is not None and rtt.count:
                rtt_block = {
                    "count": rtt.count,
                    "mean_us": round(rtt.sum / rtt.count * 1e6, 1),
                    "p50_us": round((rtt.quantile(0.5) or 0.0) * 1e6, 1),
                    "p99_us": round((rtt.quantile(0.99) or 0.0) * 1e6, 1),
                }
            block = {
                "tokens_per_sec": round(delivered / max(makespan, 1e-9), 2),
                "tokens_delivered": delivered,
                "ttft_p50_ms": round(pct(ttfts, 50) * 1000, 2),
                "ttft_p99_ms": round(pct(ttfts, 99) * 1000, 2),
                "makespan_s": round(makespan, 3),
                "finish_reasons": reasons,
                "frame_rtt": rtt_block,
                "recompiles": recompiles,
                "host_transfers": host_transfers,
            }
            return block, tokens
        finally:
            router.close()

    pipe_block, pipe_tokens = run_fleet("pipe")
    socket_block, socket_tokens = run_fleet("socket")
    _token_agreement(pipe_tokens, socket_tokens, "the socket-transport fleet")
    overhead = None
    if pipe_block["frame_rtt"] and socket_block["frame_rtt"]:
        overhead = round(
            socket_block["frame_rtt"]["p50_us"] - pipe_block["frame_rtt"]["p50_us"], 1
        )
    return {
        "pipe": pipe_block,
        "socket": socket_block,
        # Median frame RTT delta, socket minus pipe: the loopback TCP hop's
        # per-call cost over the spawned-pipe baseline (negative = noise; the
        # median, because the histogram is cumulative and warmup's compile
        # roundtrips own the mean and the tail).
        "frame_rtt_overhead_us": overhead,
        "tokens_match": True,  # asserted above; pinned in the artifact
    }


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", default="standard", choices=["standard", "ramp"],
                        help="standard: the static-vs-continuous A/B suite; ramp: the "
                        "open-loop arrival ramp (p99 TTFT vs offered load + knee-point "
                        "capacity), against an in-process or --out-of-process fleet")
    parser.add_argument("--out-of-process", action="store_true",
                        help="ramp workload: serve through REAL subprocess engine workers "
                        "(accelerate_tpu.worker) instead of in-process engines")
    parser.add_argument("--transport", default="pipe", choices=["pipe", "socket"],
                        help="out-of-process worker transport: the spawned stdio pipe, or "
                        "a loopback TCP socket (the worker self-listens, the controller "
                        "dials and handshakes) — applies to the --out-of-process ramp "
                        "fleet; the standard workload runs the pipe-vs-socket A/B either "
                        "way (extra.transport) unless --no-transport-ab")
    parser.add_argument("--no-transport-ab", action="store_true",
                        help="skip the pipe-vs-socket transport A/B (extra.transport)")
    parser.add_argument("--ramp-levels", type=int, default=5,
                        help="offered-load levels in the ramp (each doubles the rate)")
    parser.add_argument("--ramp-base-rate", type=float, default=4.0,
                        help="ramp starting offered load in requests per virtual second")
    parser.add_argument("--ramp-requests", type=int, default=None,
                        help="requests per ramp level (default: --requests)")
    parser.add_argument("--ramp-knee-factor", type=float, default=3.0,
                        help="knee = highest rate with p99 TTFT within this factor of "
                        "the unloaded level")
    parser.add_argument("--model", default=None, help="named model (accelerate_tpu.models); default llama-1b on accelerators, llama-tiny on CPU")
    parser.add_argument("--requests", type=int, default=None)
    parser.add_argument("--num-slots", type=int, default=4)
    parser.add_argument("--chunk-size", type=int, default=8)
    parser.add_argument("--prompt-min", type=int, default=8)
    parser.add_argument("--prompt-max", type=int, default=None, help="default 256 on accelerators, 96 on CPU")
    parser.add_argument("--max-new-min", type=int, default=8)
    parser.add_argument("--max-new-max", type=int, default=None, help="default 128 on accelerators, 32 on CPU")
    parser.add_argument("--max-length", type=int, default=None)
    parser.add_argument("--mean-interarrival", type=float, default=0.02, help="Poisson arrival mean gap (virtual seconds)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--page-size", type=int, default=16, help="KV pool page size in tokens (paged cache)")
    parser.add_argument("--prefix-tokens", type=int, default=None,
                        help="shared system-prompt length for the prefix-heavy workload; default 64 on accelerators, 24 on CPU; 0 disables")
    parser.add_argument("--no-speculative", action="store_true",
                        help="skip the speculative-decode A/B workload")
    parser.add_argument("--draft-tokens", type=int, default=4,
                        help="draft tokens per verify step in the speculative workload")
    parser.add_argument("--draft-ngram", type=int, default=2,
                        help="n-gram length the speculative drafter matches on")
    parser.add_argument("--attention-impl", default="xla", choices=["xla", "pallas_paged"],
                        help="decode/verify attention implementation for the main engine and "
                        "the --replicas fleet: the XLA gather oracle or the fused Pallas "
                        "page-walk kernels (paged cache only)")
    parser.add_argument("--no-attention-ab", action="store_true",
                        help="skip the kernel-vs-XLA attention A/B workload")
    parser.add_argument("--weight-dtype", default="bf16", choices=["bf16", "int8"],
                        help="weight storage dtype for the main engine, the attention A/B "
                        "and the fleet workloads: int8 = per-output-channel weight-only "
                        "quantization with the fused epilogue matmul (ops/quantization.py)")
    parser.add_argument("--kv-cache-dtype", default="bf16", choices=["bf16", "int8", "fp8_e4m3"],
                        help="KV page-pool storage dtype for the same engines: int8/fp8_e4m3 "
                        "store pages quantized with per-page-per-head scale pools, with "
                        "dequant fused into the Pallas decode kernels (paged cache only)")
    parser.add_argument("--no-quant-ab", action="store_true",
                        help="skip the quantization A/B workload (bf16 vs int8 weights + "
                        "int8/fp8 KV cache on the same workload)")
    parser.add_argument("--tp", type=int, default=1,
                        help="tensor-parallel A/B: serve the same workload through a "
                        "single-device engine and ONE engine spanning a --tp-device "
                        "submesh (Megatron-sharded weights, KV pool sharded by KV "
                        "head) — token parity asserted, per-chip bytes recorded in "
                        "extra.tensor_parallel; 1 disables")
    parser.add_argument("--sharding", default="rules", choices=["rules", "auto"],
                        help="partition source for the --tp engines: the model family's "
                        "hand-written table, or the cost-model planner's emitted one "
                        "(parallel/planner.py); the rules-vs-auto A/B in "
                        "extra.sharding_plan runs either way unless --no-sharding-ab")
    parser.add_argument("--no-sharding-ab", action="store_true",
                        help="skip the sharding rules-vs-auto A/B (extra.sharding_plan)")
    parser.add_argument("--replicas", type=int, default=1,
                        help="run the replicated-router workload over N engines with a "
                        "kill-one-replica A/B (throughput dip + recovery time); 1 disables")
    parser.add_argument("--trace-dir", default=None,
                        help="flight-recorder trace dir (span JSONL + Perfetto dump); default: a fresh temp dir — the artifact path is emitted in extra.telemetry.trace")
    args = parser.parse_args(argv)

    import jax

    from accelerate_tpu.models import create_named_model, get_model_family
    from accelerate_tpu.serving import ContinuousBatcher

    from accelerate_tpu.utils.environment import configure_compile_cache

    configure_compile_cache()
    on_accel = jax.devices()[0].platform in ("tpu", "gpu")
    # Defaults that depend on the platform, printed with the result
    # (extra.sizes) so a run always says which size it took.
    sizes_from_platform = {}
    model_name = args.model
    if model_name is None:
        model_name = sizes_from_platform["model"] = "llama-1b" if on_accel else "llama-tiny"
    for name, accel, cpu in (
        ("requests", 32, 12), ("prompt_max", 256, 96),
        ("prefix_tokens", 64, 24), ("max_new_max", 128, 32),
    ):
        if getattr(args, name) is None:
            setattr(args, name, accel if on_accel else cpu)
            sizes_from_platform[name] = getattr(args, name)
    if args.ramp_requests is None:
        args.ramp_requests = args.requests
    if args.prompt_min > args.prompt_max:
        parser.error(f"--prompt-min {args.prompt_min} > --prompt-max {args.prompt_max}")
    if args.max_new_min > args.max_new_max:
        parser.error(f"--max-new-min {args.max_new_min} > --max-new-max {args.max_new_max}")

    _fam, cfg = get_model_family(model_name)
    max_length = args.max_length or min(
        cfg.max_position_embeddings, args.prompt_max + args.max_new_max
    )
    if args.prompt_max + args.max_new_max > max_length:
        args.prompt_max = max_length - args.max_new_max
        log(f"capping prompt_max to {args.prompt_max} for the {max_length}-token cache")
        if args.prompt_max < args.prompt_min:
            parser.error(
                f"--max-length {max_length} leaves room for prompts up to "
                f"{args.prompt_max} after --max-new-max {args.max_new_max}, "
                f"below --prompt-min {args.prompt_min}"
            )

    log(f"model {model_name} | slots {args.num_slots} chunk {args.chunk_size} | "
        f"{args.requests} reqs, prompts {args.prompt_min}-{args.prompt_max}, "
        f"max_new {args.max_new_min}-{args.max_new_max}, cache {max_length}")
    model = create_named_model(
        model_name, seq_len=min(128, max_length), param_dtype="bfloat16" if on_accel else None
    )
    rng = np.random.default_rng(args.seed)
    prompts, budgets, arrivals = build_workload(args, cfg.vocab_size, rng)

    from accelerate_tpu.generation import Generator
    from accelerate_tpu.telemetry import FlightRecorder
    from accelerate_tpu.telemetry.tracing import Tracer

    # Request-scoped tracing rides the whole bench: every request's
    # submit->finish span streams into the trace dir, and the Perfetto dump
    # path lands in the JSON (extra.telemetry.trace) so a bench artifact links
    # straight to its timeline. The armed TraceGuard below is the pin that
    # this instrumentation costs 0 recompiles / 0 host transfers.
    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="serving_bench_trace_")
    tracer = Tracer(recorder=FlightRecorder(log_dir=trace_dir), category="serve")

    if args.workload == "ramp":
        ramp = run_ramp_workload(model, args, cfg, max_length, rng, tracer=tracer)
        prefix = "" if on_accel else "cpu-smoke "
        topo = ", out-of-process" if args.out_of_process else ""
        result = {
            "metric": f"{prefix}serving capacity knee (open-loop ramp, {model_name}, "
            f"{ramp['replicas']} replica(s){topo})",
            "value": ramp["knee"]["offered_tokens_per_sec"],
            "unit": "offered tokens/sec at the p99-TTFT knee",
            "extra": {
                "device_kind": jax.devices()[0].device_kind,
                "platform": jax.devices()[0].platform,
                "sizes": {"platform_defaults": sizes_from_platform},
                "ramp_workload": ramp,
                "num_slots": args.num_slots,
                "chunk_size": args.chunk_size,
                "prompt_range": [args.prompt_min, args.prompt_max],
                "max_new_range": [args.max_new_min, args.max_new_max],
                "seed": args.seed,
            },
        }
        print(json.dumps(result))
        return 0

    engine = ContinuousBatcher(
        model, num_slots=args.num_slots, max_length=max_length, chunk_size=args.chunk_size,
        page_size=args.page_size, tracer=tracer,
        max_queue=args.requests, attention_impl=args.attention_impl,
        weight_dtype=args.weight_dtype, kv_cache_dtype=args.kv_cache_dtype,
    )
    static_gen = Generator(model, max_new_tokens=max(budgets), max_length=max_length)

    # Warmup pass: compile every program both paths use (static per batch shape,
    # continuous per insert bucket + the one chunk program), then measure.
    # `warm_inserts` precompiles the engine's CLOSED insert-bucket ladder — a
    # mechanical guarantee that no admission of the timed pass can mint a fresh
    # bucket, whatever prefix-cache depth it arrives at (the first-hit insert
    # recompile that used to trip the 0-recompile assert at non-default
    # --max-new-max / --page-size combinations). The continuous path still
    # warms TWICE: the first pass registers prompt prefixes, so the second
    # runs the prefix-HIT suffix path end to end before timing.
    log("warmup (compiles)...")
    t0 = time.perf_counter()
    run_static(static_gen, prompts, budgets, arrivals, args.num_slots, max_length)
    log(f"insert buckets warmed: {engine.warm_inserts()}")
    run_continuous(engine, prompts, budgets, arrivals)
    # Impl provenance: the decode chunk traced during the pass above (after
    # every insert bucket), and LAST_DISPATCH is a trace-time record — it
    # still names the attention implementation the MAIN engine's one decode
    # executable actually chose, which the JSON pins next to the flag.
    from accelerate_tpu.ops import attention as attention_ops

    main_dispatch_impl = attention_ops.LAST_DISPATCH
    run_continuous(engine, prompts, budgets, arrivals)
    log(f"warmup done in {time.perf_counter() - t0:.1f}s; timed runs...")

    # Steady state runs ARMED: every executable is warm, so the timed passes
    # must neither recompile nor make a guarded (implicit) host transfer — the
    # counters land in the bench JSON and 0/0 is the regression gate. The
    # engine's fault isolation `observe()`s violations it swallows, so they
    # reach this ledger even when serving keeps running.
    from accelerate_tpu.analysis import TraceGuard

    guard = TraceGuard(transfer_guard="disallow", on_violation="record", name="serving-bench")
    engine.trace_guard = guard
    with guard:
        s_tps, s_ttft, s_iters, s_span = run_static(
            static_gen, prompts, budgets, arrivals, args.num_slots, max_length
        )
        c_tps, c_ttft, c_iters, c_span = run_continuous(engine, prompts, budgets, arrivals)
    if guard.total_recompiles or guard.host_transfers:
        log(f"TRACE-GUARD VIOLATIONS in steady state: {guard.report().summary()}")
    assert engine.trace_counts["decode_chunk"] == 1, engine.trace_counts
    # The tracing-overhead pin: span instrumentation (request lifecycles,
    # insert/chunk spans) rides the timed passes above — it must not have
    # cost a single recompile or guarded host transfer.
    assert guard.total_recompiles == 0 and guard.host_transfers == 0, (
        "timed passes regressed the 0-recompile / 0-host-transfer discipline "
        f"with tracing enabled: {guard.report().summary()}"
    )

    # Prefix-heavy workload: same model, shared system prompt across requests,
    # prefix cache ON vs OFF.
    prefix_block = None
    if args.prefix_tokens > 0:
        max_prefix = max_length - args.max_new_max - args.prompt_min
        if args.prefix_tokens > max_prefix:
            log(f"capping prefix_tokens to {max_prefix} for the {max_length}-token cache")
            args.prefix_tokens = max_prefix
        if args.prefix_tokens >= args.page_size:
            prefix_block = run_prefix_workload(model, args, cfg, max_length, rng, tracer=tracer)
        else:
            log(
                f"prefix_tokens {args.prefix_tokens} < page_size {args.page_size}: "
                "no full page to share; skipping the prefix workload"
            )

    # Speculative-decode A/B: repetition-heavy workload, speculation off vs on,
    # TraceGuard-armed timed passes (hard 0/0 gate with speculation enabled).
    spec_block = None
    if not args.no_speculative:
        spec_block = run_spec_workload(model, args, cfg, max_length, rng, tracer=tracer)
        if (spec_block["accepted_tokens_per_step"] or 0) <= 1.0:
            log(
                "speculation accepted no drafts on the repetitive workload "
                f"(accepted_tokens_per_step={spec_block['accepted_tokens_per_step']}) "
                "— output is still token-identical, but check drafter knobs"
            )

    # Kernel-vs-XLA attention A/B: the SAME workload as the headline timed
    # passes through two otherwise-identical paged engines, so the JSON
    # records both impls' decode tokens/sec plus the pool-geometry HBM
    # estimate — the bandwidth claim as an artifact.
    attention_ab = None
    if not args.no_attention_ab:
        attention_ab = run_attention_workload(
            model, args, cfg, max_length, (prompts, budgets, arrivals), tracer=tracer
        )

    # Quantization A/B: bf16 vs int8-weights+int8-KV vs int8-weights+fp8-KV on
    # the same workload — tokens/sec, per-dispatch attention seconds, actual
    # pool/weight bytes, token agreement and the >= 2x cache-byte drop gate.
    quant_block = None
    if not args.no_quant_ab:
        quant_block = run_quant_workload(
            model, args, cfg, max_length, (prompts, budgets, arrivals), tracer=tracer
        )

    # Tensor-parallel A/B (--tp N): tp=1 vs one engine spanning N devices on
    # the same workload — token parity and the ~1/N per-chip footprint drop
    # asserted, per-chip bytes read off the live shardings.
    tp_block = None
    if args.tp > 1:
        tp_block = run_tensor_parallel_workload(
            model, args, cfg, max_length, (prompts, budgets, arrivals), tracer=tracer
        )

    # Sharding-source A/B (--tp N): hand rules vs the planner's auto table on
    # the same mesh — token identity + the >= 60%-of-ideal footprint asserted,
    # the planner's predicted-vs-measured step time reported.
    sharding_block = None
    if args.tp > 1 and not args.no_sharding_ab:
        sharding_block = run_sharding_plan_workload(
            model, args, cfg, max_length, (prompts, budgets, arrivals), tracer=tracer
        )

    # Replicated-router A/B: the same workload behind a health-routed fleet,
    # with one replica chaos-killed mid-traffic (dip + recovery measured).
    router_block = None
    if args.replicas > 1:
        router_block = run_router_workload(model, args, cfg, max_length, rng, tracer=tracer)

    # Pipe-vs-socket transport A/B: the same workload through two
    # out-of-process fleets over loopback — the socket hop's cost (frame RTT,
    # TTFT, tokens/sec) as an artifact, token parity + per-worker 0/0 asserted.
    # An accelerator belongs to one process, and this one holds it: worker
    # processes could not reach the chip, so the A/B runs on CPU only.
    transport_block = None
    if on_accel and not args.no_transport_ab:
        log("transport A/B skipped: subprocess workers cannot share the chip this process holds")
        transport_block = {"skipped": "parent process holds the accelerator"}
    elif not args.no_transport_ab:
        transport_block = run_transport_workload(
            model, args, cfg, max_length, rng, tracer=tracer
        )

    speedup = c_tps / max(s_tps, 1e-9)
    prefix = "" if on_accel else "cpu-smoke "

    # Telemetry block: the engine's MetricsRegistry view of the SAME run —
    # real-wall-clock TTFT / inter-token / chunk latency histograms (cumulative
    # over warmup + timed passes; the virtual-clock numbers above stay the
    # headline) plus occupancy gauges. docs/observability.md documents the
    # instruments.
    registry = engine.metrics

    def _hist_ms(name):
        hist = registry.get(name)
        if hist is None or hist.count == 0:
            return None
        return {
            "count": hist.count,
            "p50_ms": round((hist.quantile(0.5) or 0.0) * 1000, 3),
            "p99_ms": round((hist.quantile(0.99) or 0.0) * 1000, 3),
        }

    # Per-phase span counts + the Perfetto artifact: how many request
    # lifecycles, admission dispatches and decode chunks the recorder saw
    # (ring-bounded — the JSONL streams in trace_dir carry the full history).
    span_counts = {}
    for record in tracer.recorder.records():
        if record.get("kind") == "span":
            span_counts[record["name"]] = span_counts.get(record["name"], 0) + 1
    trace_artifact = tracer.recorder.dump(reason="bench")

    telemetry_block = {
        "ttft": _hist_ms("serving_ttft_seconds"),
        "inter_token": _hist_ms("serving_inter_token_seconds"),
        "chunk": _hist_ms("serving_chunk_seconds"),
        "queue_peak": registry.value("serving_queue_peak"),
        "slot_utilization": registry.value("serving_slot_utilization"),
        "requests_submitted": registry.value("serving_requests_submitted_total"),
        "pages_total": registry.value("serving_pages_total"),
        "pages_in_use": registry.value("serving_pages_in_use"),
        "prefix_cache_hits": registry.value("serving_prefix_cache_hits_total"),
        "prefix_cache_misses": registry.value("serving_prefix_cache_misses_total"),
        "prefix_cache_evictions": registry.value("serving_prefix_cache_evictions_total"),
        "prefill_tokens_saved": registry.value("prefill_tokens_saved_total"),
        "trace": {
            "artifact": trace_artifact,
            "trace_dir": trace_dir,
            "span_counts": span_counts,
        },
    }
    paging_block = dict(
        enabled=True,
        page_size=args.page_size,
        pages_total=engine.stats["pages_total"],
        kv_cache_dtype=engine.stats["kv_cache_dtype"],
        prefix_cache=engine.stats["prefix_cache"],
    )
    result = {
        "metric": f"{prefix}continuous-batching serving tokens/sec "
        f"({model_name}, slots {args.num_slots}, chunk {args.chunk_size}, "
        f"{args.requests} mixed reqs)",
        "value": round(c_tps, 2),
        "unit": "tokens/sec",
        # baseline = the static path measured in THIS run: apples-to-apples on
        # any backend (higher is better).
        "vs_baseline": round(speedup, 3),
        "extra": {
            "device_kind": jax.devices()[0].device_kind,
            "platform": jax.devices()[0].platform,
            "sizes": {"platform_defaults": sizes_from_platform},
            "static_tokens_per_sec": round(s_tps, 2),
            "continuous_tokens_per_sec": round(c_tps, 2),
            "speedup": round(speedup, 3),
            "ttft_p50_ms_static": round(pct(s_ttft, 50) * 1000, 2),
            "ttft_p99_ms_static": round(pct(s_ttft, 99) * 1000, 2),
            "ttft_p50_ms_continuous": round(pct(c_ttft, 50) * 1000, 2),
            "ttft_p99_ms_continuous": round(pct(c_ttft, 99) * 1000, 2),
            "decode_iterations_static": s_iters,
            "decode_iterations_continuous": c_iters,
            # Engine health ledger (cumulative over warmup + timed passes):
            # how close the queue ran to its backpressure limit, and where
            # every request ended up (all "length" on this EOS-free workload —
            # any timeout/error/cancelled here is a bench regression).
            "queue_peak": engine.stats["queue_peak"],
            "finish_reasons": dict(engine.stats["finish_reasons"]),
            "telemetry": telemetry_block,
            # Attention-impl provenance + the kernel-vs-XLA A/B: which
            # implementation the main engine's decode executable traced, and
            # both impls' decode tokens/sec / per-dispatch seconds / estimated
            # HBM bytes from the same workload (docs/observability.md).
            "attention": {
                "impl": args.attention_impl,
                "dispatch_impl": main_dispatch_impl,
                # pallas_paged off-TPU = the Pallas INTERPRETER (the kernels'
                # interpret=None auto-select): parity and the 0/0 discipline
                # hold, the timing is not kernel timing.
                "interpreted": (
                    args.attention_impl == "pallas_paged"
                    and jax.default_backend() != "tpu"
                ),
                "ab": attention_ab,
            },
            # Quantization A/B (bf16 vs int8 weights + int8/fp8 KV cache):
            # the bandwidth/capacity multipliers as artifacts — tokens/sec,
            # per-dispatch seconds, actual pool + weight bytes, estimated
            # cache-read HBM drop (>= 2x asserted), token agreement vs bf16,
            # max logit error of the int8-weight forward, interpreter
            # provenance. Main-engine dtypes are pinned next to it.
            "quantization": {
                "weight_dtype": args.weight_dtype,
                "kv_cache_dtype": args.kv_cache_dtype,
                "ab": quant_block,
            },
            # Paged-KV state of the MAIN engine plus the shared-system-prompt
            # A/B (prefix cache on/off); prefill_tokens_saved > 0 with TTFT no
            # worse than the uncached run is the prefix-cache acceptance gate.
            "paging": paging_block,
            "prefix_workload": prefix_block,
            # Speculative A/B (repetition-heavy workload): tokens/sec and
            # accepted_tokens_per_step, spec-off vs spec-on, both timed passes
            # TraceGuard-verified at 0 recompiles / 0 host transfers.
            "speculative_workload": spec_block,
            # Tensor-parallel A/B (--tp N): tp=1 vs one mesh-spanning engine
            # on the same workload — tokens/sec, per-dispatch attention
            # seconds, per-chip weight + KV-pool bytes from live shardings
            # (~1/N asserted), greedy token identity asserted, TraceGuard
            # 0/0 per row (docs/observability.md).
            "tensor_parallel": tp_block,
            # hand rules vs planner auto on the same mesh: per-chip bytes off
            # live shardings for BOTH plans + predicted-vs-measured step error
            "sharding_plan": sharding_block,
            # Replicated-fleet A/B (--replicas N): baseline vs kill-one-replica
            # throughput, degraded-window tokens/sec, measured recovery
            # seconds, retry/replica_lost accounting — still 0 recompiles /
            # 0 host transfers per engine.
            "router_workload": router_block,
            # Pipe-vs-socket transport A/B over loopback subprocess fleets:
            # tokens/sec, TTFT p50/p99 and frame RTT per transport, the
            # socket hop's mean RTT overhead, greedy token parity, per-worker
            # 0/0 guards.
            "transport": transport_block,
            # Steady-state discipline counters (TraceGuard armed over both
            # timed passes): any nonzero value is a no-recompile regression.
            "recompiles": guard.total_recompiles,
            "host_transfers": guard.host_transfers,
            "recompiled_executables": dict(guard.compiles),
            "makespan_s_static": round(s_span, 3),
            "makespan_s_continuous": round(c_span, 3),
            "requests": args.requests,
            "num_slots": args.num_slots,
            "chunk_size": args.chunk_size,
            "prompt_range": [args.prompt_min, args.prompt_max],
            "max_new_range": [args.max_new_min, args.max_new_max],
            "mean_interarrival_s": args.mean_interarrival,
            "seed": args.seed,
        },
    }
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
