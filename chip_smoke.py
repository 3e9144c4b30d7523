#!/usr/bin/env python3
"""Chip smoke: the serving engine and the trainer, end to end, on a real TPU.

    python chip_smoke.py             # one chip: device, serve, serve-kernel, train, train-flash
    python chip_smoke.py --chips 4   # four chips: ONLY the multi-chip phase and its baselines

One process owns the chip(s); every phase runs in it, prints one JSON line when it
finishes (wall / compile / run seconds, compile-cache hits and misses, what was
compared and the result) and frees its params, caches and optimizer state before
the next starts. A phase that fails raises: the run exits non-zero. There is no
CPU mode — without a TPU the device phase prints `{"ok": false, ...}` and exits 1.
Weights and data come from `--seed`; nothing is read from the network or from git.

The last line of stdout is exactly
`{"ok": true, "device": {"platform": "tpu", "kind": "<device_kind>", "count": N}}`.

Parity checks compare TOKENS and judge divergences by LOGITS: seeded random
weights give nearly flat logits (top-1/top-2 gap ~0.2 at a 128k vocabulary), so two
correct bf16 paths flip a greedy token now and then on summation order alone. A
float32 teacher-forced forward of the same params is the reference: each path's
prefill and first-decode-step choice must sit within `LOGIT_TOL` of the
reference's best logit, and where two paths first differ, the reference's logit
gap between their two choices must be within `LOGIT_TOL` too. The share of
agreeing tokens is reported, not required.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import sys
import time

import numpy as np

#: Largest float32-reference logit gap a bf16 path's greedy choice may have to the
#: reference's best (and two paths' choices to each other at their first
#: divergence). bf16 carries 8 mantissa bits through 16 layers; a wrong mask, page
#: or scale moves logits by O(1).
LOGIT_TOL = 0.25
#: int8 KV pages add ~1/127 relative rounding per cached value on top of bf16.
LOGIT_TOL_INT8 = 0.5


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What each phase runs at. `SMOKE` is the chip run; tests/test_chip_smoke.py
    drives the same phase functions at `TINY` on CPU (the script itself has no
    option that selects it)."""

    serve_model: str = "llama-1b"
    serve_dtype: str = "bfloat16"
    #: prompt lengths, each used for two requests: 3 insert buckets, 4 static prefills
    prompt_lens: tuple = (32, 100, 180, 256)
    new_tokens: tuple = (32, 64)  # per-request budget drawn from this closed range
    num_slots: int = 4
    chunk_size: int = 8
    train_model: str = "bert-base"
    train_batch: int = 64
    train_examples: int = 256  # the MRPC-shaped set the steps cycle over, epoch by epoch
    train_lr: float = 1e-4
    fused_steps: int = 20
    eager_steps: int = 5
    flash_model: str = "llama-1b"
    flash_seq: int = 1024
    flash_batch: int = 2  # from memory_analysis() of the rehearsal compile, see CHANGES.md
    flash_steps: int = 3
    tp_train_batch: int = 32  # --chips 4: global batch of the DP/ZeRO comparison


SMOKE = Sizes()
TINY = Sizes(
    serve_model="llama-tiny", serve_dtype="float32", prompt_lens=(5, 20),
    new_tokens=(4, 8), train_model="bert-tiny", train_batch=8, train_examples=32,
    train_lr=1e-3, fused_steps=16, eager_steps=2, flash_model="llama-tiny", flash_seq=128,
    flash_batch=8, flash_steps=2, tp_train_batch=8,
)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


class CompileLedger:
    """Compile seconds and persistent-cache traffic, from `jax.monitoring`."""

    #: XLA/Mosaic compile (or the persistent cache's retrieval). Tracing and lowering
    #: are not in it — their events nest, and would count twice — so `run_s` holds them.
    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.compile_s = 0.0
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_):
        if event == self._COMPILE:
            self.compile_s += duration

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.compile_s, self.requests, self.hits


@contextlib.contextmanager
def phase(name: str, ledger: CompileLedger):
    """Time one phase and print its line. A failure prints `"ok": false` and
    re-raises — the process exits non-zero with the traceback."""
    record: dict = {"phase": name}
    compile0, requests0, hits0 = ledger.snapshot()
    t0 = time.perf_counter()
    try:
        yield record
    except BaseException as exc:
        emit({"phase": name, "ok": False, "error": f"{type(exc).__name__}: {exc}"[:2000]})
        raise
    wall = time.perf_counter() - t0
    compile1, requests1, hits1 = ledger.snapshot()
    compile_s = min(compile1 - compile0, wall)
    record.update(
        ok=True,
        wall_s=round(wall, 3),
        compile_s=round(compile_s, 3),
        run_s=round(wall - compile_s, 3),
        compile_cache={
            "requests": requests1 - requests0,
            "hits": hits1 - hits0,
            "misses": (requests1 - requests0) - (hits1 - hits0),
        },
    )
    emit(record)


def release_device_memory() -> None:
    """Between phases: drop compiled programs and collect what the phase let go of
    (llama-1b serving and llama-1b training do not fit 16 GB together)."""
    import jax

    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    gc.collect()
    jax.clear_caches()
    gc.collect()


def peak_hbm_gb():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return round(peak / 2**30, 3) if peak else None


# ------------------------------------------------------------------------- device
def device_info(cache_dir: str) -> dict:
    import importlib.metadata

    import jax
    import jaxlib

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": jax.device_count(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "compile_cache_dir": cache_dir,
        # a cap under the run's ~0.3 GB of executables evicts (LRU) before a re-run reads
        "compile_cache_max_bytes": jax.config.jax_compilation_cache_max_size,
    }


def fence_check(record: dict, n: int = 8192, chain: int = 8) -> None:
    """Does `block_until_ready` fence? Time a matmul chain three ways — the call
    returning, `block_until_ready`, a data-dependent host read — and hold the
    fenced time to the physical floor FLOPs / peak."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.utils.environment import get_device_peak_flops

    @jax.jit
    def chained(x):
        for _ in range(chain):
            x = (x @ x) * jnp.bfloat16(1.0 / n)
        return x

    x = jnp.ones((n, n), jnp.bfloat16)
    jax.block_until_ready(chained(x))
    t0 = time.perf_counter()
    y = chained(x)
    returned = time.perf_counter() - t0
    jax.block_until_ready(y)
    blocked = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(jax.device_get(chained(x)[0, 0]))
    readback = time.perf_counter() - t0
    flops = chain * 2 * n**3
    peak = get_device_peak_flops(jax.devices()[0].device_kind)
    record["fence"] = {
        "call_returned_ms": round(returned * 1e3, 3),
        "block_until_ready_ms": round(blocked * 1e3, 3),
        "host_readback_ms": round(readback * 1e3, 3),
        "floor_ms_at_peak": round(flops / peak * 1e3, 3),
        "tflops_at_block_until_ready": round(flops / blocked / 1e12, 1),
    }
    if blocked < flops / peak:
        raise AssertionError(
            f"block_until_ready returned in {blocked * 1e3:.2f} ms, under the "
            f"{flops / peak * 1e3:.2f} ms floor at peak: it did not wait for the device"
        )


def phase_device(ledger: CompileLedger, cache_dir: str, chips: int) -> dict:
    info = device_info(cache_dir)
    if info["platform"] != "tpu":
        emit({"ok": False, "phase": "device", "error": "no TPU: JAX found no accelerator", **info})
        raise SystemExit(1)
    if info["count"] != chips:
        emit({"ok": False, "phase": "device",
              "error": f"this run needs {chips} chip(s), JAX reports {info['count']}", **info})
        raise SystemExit(1)
    with phase("device", ledger) as record:
        from accelerate_tpu.parallel.planner import chip_for_device_kind
        from accelerate_tpu.utils.environment import get_device_peak_flops

        record.update(info)
        # Both tables must KNOW this chip's device_kind string (unknown raises).
        record["peak_bf16_tflops"] = get_device_peak_flops(info["kind"]) / 1e12
        record["planner_chip"] = chip_for_device_kind(info["kind"]).name
        fence_check(record)
    return info


# -------------------------------------------------------------------------- serve
def build_serve_model(sizes: Sizes, seed: int):
    """`commands/serve.py`'s construction: a named in-tree model, seeded weights."""
    import jax

    from accelerate_tpu.models import create_named_model, get_model_family

    _family, cfg = get_model_family(sizes.serve_model)
    max_length = max(sizes.prompt_lens) + sizes.new_tokens[1]
    model = create_named_model(
        sizes.serve_model, seq_len=min(128, max_length), rng=jax.random.key(seed),
        param_dtype=sizes.serve_dtype,
    )
    return model, cfg, max_length


def make_requests(sizes: Sizes, vocab_size: int, seed: int):
    from accelerate_tpu.serving import Request

    rng = np.random.default_rng(seed)
    lens = [n for n in sizes.prompt_lens for _ in range(2)]
    return [
        Request(
            i,
            rng.integers(1, vocab_size, (n,)).astype(np.int32),
            max_new_tokens=int(rng.integers(sizes.new_tokens[0], sizes.new_tokens[1] + 1)),
        )
        for i, n in enumerate(lens)
    ]


def serve_requests(router, requests):
    """Submit, drain, and hold every request to a normal finish: the engine's
    fault isolation turns a crashed dispatch into `finish_reason="error"`, which
    here is a failure of the phase."""
    for request in requests:
        router.submit(request)
    results = router.drain()
    tokens = {}
    for request in requests:
        result = results[request.request_id]
        if result.finish_reason not in ("eos", "length"):
            raise AssertionError(
                f"request {request.request_id} finished {result.finish_reason!r}: {result.error}"
            )
        if len(result.tokens) != request.max_new_tokens:
            raise AssertionError(
                f"request {request.request_id}: {len(result.tokens)} tokens, "
                f"asked for {request.max_new_tokens}"
            )
        tokens[request.request_id] = np.asarray(result.tokens, np.int32)
        router.release(request.request_id)
    return tokens


def make_router(model, sizes: Sizes, max_length: int, **engine_kwargs):
    from accelerate_tpu.router import Router

    return Router(
        model, replicas=1, num_slots=sizes.num_slots, max_length=max_length,
        chunk_size=sizes.chunk_size, **engine_kwargs,
    )


def static_tokens(model, requests, max_length: int, max_new: int):
    """The static `Generator` path, one request at a time (the parity tier-1 pins
    on CPU): `max_new` greedy tokens per prompt."""
    from accelerate_tpu.generation import GenerationConfig, Generator

    generator = Generator(model, max_new_tokens=max_new, max_length=max_length)
    config = GenerationConfig(max_new_tokens=max_new)
    out = {}
    for request in requests:
        prompt = np.asarray(request.input_ids, np.int32)
        full = np.asarray(generator(prompt[None, :], config))
        out[request.request_id] = full[0, prompt.size:]
    return out


class Reference:
    """Float32 teacher-forced reference: one full-sequence forward (no KV cache)
    of prompt + an oracle's tokens per request."""

    def __init__(self, model, max_length: int):
        import jax
        import jax.numpy as jnp

        self.params = model.params if "params" in model.params else {"params": model.params}
        self.max_length = max_length

        @jax.jit
        def forward(params, ids):
            params = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float32) if jnp.issubdtype(x.dtype, jnp.floating) else x,
                params,
            )
            with jax.default_matmul_precision("float32"):
                return model.apply_fn(params, ids).astype(jnp.float32)

        self._forward = forward

    def rows(self, requests, oracle_tokens) -> dict:
        """Per request, the logits that predict each generated token, GIVEN the
        oracle's tokens before it: row j is valid for judging any path that
        agrees with the oracle on tokens < j."""
        import jax
        import jax.numpy as jnp

        rows = {}
        for request in requests:
            prompt = np.asarray(request.input_ids, np.int32)
            generated = oracle_tokens[request.request_id]
            ids = np.zeros((1, self.max_length), np.int32)  # right pads: causal, never seen
            ids[0, : prompt.size] = prompt
            ids[0, prompt.size : prompt.size + generated.size - 1] = generated[:-1]
            logits = self._forward(self.params, jnp.asarray(ids))
            span = logits[0, prompt.size - 1 : prompt.size - 1 + request.max_new_tokens]
            rows[request.request_id] = np.asarray(jax.device_get(span))
        return rows


def compare_tokens(name, requests, got, oracle, ref_rows, tol: float) -> dict:
    """Token agreement, judged by the float32 reference logits (module docstring)."""
    agree = total = identical = 0
    worst_choice = worst_gap = 0.0
    for request in requests:
        rid, n = request.request_id, request.max_new_tokens
        a, b, ref = got[rid][:n], oracle[rid][:n], ref_rows[rid]
        same = a == b
        agree += int(same.sum())
        total += n
        identical += int(same.all())
        # Up to the first divergence both paths saw the oracle's prefix, so the
        # reference rows (teacher-forced on that prefix) judge both choices.
        first = n if same.all() else int(np.argmin(same))
        for j in range(min(first + 1, n)):
            if j < 2 or j == first:  # prefill, first decode step, the divergence
                shortfall = float(ref[j].max() - ref[j, a[j]])
                worst_choice = max(worst_choice, shortfall)
                if shortfall > tol:
                    raise AssertionError(
                        f"{name}: request {rid} token {j} = {int(a[j])} sits {shortfall:.3f} "
                        f"below the float32 reference's best logit (tolerance {tol})"
                    )
        if first < n:
            gap = float(abs(ref[first, a[first]] - ref[first, b[first]]))
            worst_gap = max(worst_gap, gap)
            if gap > tol:
                raise AssertionError(
                    f"{name}: request {rid} diverges at token {first} "
                    f"({int(a[first])} vs {int(b[first])}) with a reference logit gap "
                    f"of {gap:.3f} (tolerance {tol})"
                )
    return {
        "requests_identical": f"{identical}/{len(requests)}",
        "token_agreement": round(agree / total, 4),
        "max_shortfall_to_ref_best": round(worst_choice, 4),
        "max_gap_at_divergence": round(worst_gap, 4),
        "logit_tol": tol,
    }


def phase_serve(ledger, sizes: Sizes, seed: int, ctx: dict) -> None:
    """llama through `Router` -> `ContinuousBatcher` with no `attention_impl` named
    (the engine's choice: llama-1b's heads of 64 would have to be staged for the
    kernel, so it stays on the XLA read, on the chip too), held to the static
    `Generator` path; steady state under a recording TraceGuard."""
    import jax

    from accelerate_tpu.analysis import TraceGuard

    with phase("serve", ledger) as record:
        model, cfg, max_length = build_serve_model(sizes, seed)
        jax.block_until_ready(model.params)
        warm = make_requests(sizes, cfg.vocab_size, seed + 1)
        requests = make_requests(sizes, cfg.vocab_size, seed + 2)
        guard = TraceGuard(on_violation="record", name="chip-smoke-serve")
        router = make_router(model, sizes, max_length, trace_guard=guard)
        read = router.replica_set.replicas[0].engine.stats["attention_impl"]
        t0 = time.perf_counter()
        serve_requests(router, warm)  # compiles the decode chunk + every insert bucket used
        warm_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        with guard:
            tokens = serve_requests(router, requests)
        steady_s = time.perf_counter() - t0
        router.close()
        oracle = static_tokens(model, requests, max_length, sizes.new_tokens[1])
        reference = Reference(model, max_length)
        ref_rows = reference.rows(requests, oracle)
        n_tokens = sum(r.max_new_tokens for r in requests)
        record.update(
            model=sizes.serve_model,
            params_dtype=sizes.serve_dtype,
            requests=len(requests),
            prompt_lens=list(sizes.prompt_lens),
            generated_tokens=n_tokens,
            warm_pass_s=round(warm_s, 3),
            steady_pass_s=round(steady_s, 3),
            steady_tokens_per_s=round(n_tokens / steady_s, 1),
            recompiles_after_warmup=guard.total_recompiles,
            host_transfers_after_warmup=guard.host_transfers,
            vs_static_generator=compare_tokens(
                "engine vs static Generator", requests, tokens, oracle, ref_rows, LOGIT_TOL
            ),
            attention_impl=read,
            peak_hbm_gb=peak_hbm_gb(),
        )
        ctx.update(model=model, cfg=cfg, max_length=max_length, requests=requests,
                   xla_tokens=tokens, reference=reference)


def kernel_engine_tokens(ctx, sizes: Sizes, expect_kernel: bool, **engine_kwargs):
    """One fresh engine over the shared requests. For `pallas_paged` engines the
    decode program is lowered BEFORE the first dispatch, which is also its first
    trace — so `LAST_DISPATCH` is the decode program's own."""
    from accelerate_tpu.ops import attention

    router = make_router(ctx["model"], sizes, ctx["max_length"], **engine_kwargs)
    evidence = {}
    if engine_kwargs.get("attention_impl") == "pallas_paged":
        engine = router.replica_set.replicas[0].engine
        attention.LAST_DISPATCH = None
        text = engine.lower_decode_chunk().as_text()
        if attention.LAST_DISPATCH != "pallas_paged":
            raise AssertionError(f"decode program dispatched {attention.LAST_DISPATCH!r}")
        evidence = {"last_dispatch": attention.LAST_DISPATCH,
                    "tpu_custom_call": "tpu_custom_call" in text}
        if expect_kernel and not evidence["tpu_custom_call"]:
            raise AssertionError(
                "pallas_paged decode program holds no tpu_custom_call: the interpreter "
                "or the XLA oracle ran, not the compiled kernel"
            )
    tokens = serve_requests(router, ctx["requests"])
    router.close()
    return tokens, evidence


def phase_serve_kernel(ledger, sizes: Sizes, ctx: dict) -> None:
    """The same requests through the Pallas page-walk kernel, named (llama-1b's
    heads of 64 are half a lane row: the kernel stages such a pool, which the
    engine's own choice never does): bf16 pages against the serve phase's XLA
    engine, int8 pages against the quantized XLA oracle."""
    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    with phase("serve-kernel", ledger) as record:
        requests, reference = ctx["requests"], ctx["reference"]
        bf16, evidence = kernel_engine_tokens(ctx, sizes, on_tpu, attention_impl="pallas_paged")
        # Each oracle gets reference rows teacher-forced on ITS OWN sequences.
        record["bf16"] = {
            **evidence,
            **compare_tokens("pallas_paged bf16 vs xla", requests, bf16, ctx["xla_tokens"],
                             reference.rows(requests, ctx["xla_tokens"]), LOGIT_TOL),
        }
        int8_oracle, _ = kernel_engine_tokens(ctx, sizes, on_tpu, attention_impl="xla", kv_cache_dtype="int8")
        int8, evidence = kernel_engine_tokens(
            ctx, sizes, on_tpu, attention_impl="pallas_paged", kv_cache_dtype="int8"
        )
        record["int8"] = {
            **evidence,
            **compare_tokens("pallas_paged int8 vs xla int8", requests, int8, int8_oracle,
                             reference.rows(requests, int8_oracle), LOGIT_TOL_INT8),
        }
        record["peak_hbm_gb"] = peak_hbm_gb()


# -------------------------------------------------------------------------- train
def mrpc_batches(sizes: Sizes, vocab_size: int, seed: int):
    """The synthetic MRPC-shaped data of examples/nlp_example.py, collated into
    fixed batches the steps cycle over."""
    from examples.nlp_example import get_dataset

    data = get_dataset(vocab_size - 1, n=sizes.train_examples, seed=seed)
    batches = []
    for start in range(0, len(data) - sizes.train_batch + 1, sizes.train_batch):
        rows = data[start : start + sizes.train_batch]
        batches.append({
            "input_ids": np.stack([r["input_ids"] for r in rows]),
            "token_type_ids": np.stack([r["token_type_ids"] for r in rows]),
            "labels": np.asarray([r["labels"] for r in rows], np.int32),
        })
    return batches


def phase_train(ledger, sizes: Sizes, seed: int) -> None:
    """bert through `Accelerator.prepare`: the fused `train_step` path, then the
    eager `backward()` + `optimizer.step()` path of examples/nlp_example.py."""
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import create_named_model, get_model_family
    from accelerate_tpu.native import native_available
    from accelerate_tpu.parallel.sharding import data_spec
    from accelerate_tpu.utils import set_seed
    from jax.sharding import NamedSharding

    with phase("train", ledger) as record:
        set_seed(seed)
        _family, cfg = get_model_family(sizes.train_model)
        model = create_named_model(sizes.train_model, seq_len=128, rng=jax.random.key(seed))
        host_batches = mrpc_batches(sizes, cfg.vocab_size, seed)

        with jax.default_matmul_precision("float32"):
            ref_loss = float(jax.jit(
                lambda p, b: model.loss_fn(p, b, model.apply_fn)
            )(model.params, {k: jnp.asarray(v) for k, v in host_batches[0].items()}))

        accelerator = Accelerator(mixed_precision="bf16")
        pmodel, popt = accelerator.prepare(model, optax.adamw(sizes.train_lr))
        sharding = NamedSharding(accelerator.mesh, data_spec(accelerator.mesh))
        batches = [jax.device_put(b, sharding) for b in host_batches]

        step_fn = accelerator.train_step()
        t0 = time.perf_counter()
        fused = [step_fn(batches[i % len(batches)]) for i in range(sizes.fused_steps)]
        jax.block_until_ready((fused, pmodel.params))
        fused_s = time.perf_counter() - t0
        fused = [float(x) for x in jax.device_get(fused)]

        t0 = time.perf_counter()
        eager = []
        for i in range(sizes.eager_steps):
            with accelerator.accumulate(pmodel):
                eager.append(accelerator.backward(pmodel.loss, batches[i % len(batches)]))
                popt.step()
                popt.zero_grad()
        jax.block_until_ready((eager, pmodel.params))
        eager_s = time.perf_counter() - t0
        eager = [float(x) for x in jax.device_get(eager)]

        losses = fused + eager
        if not np.all(np.isfinite(losses)):
            raise AssertionError(f"non-finite loss: {losses}")
        # bf16 autocast against the float32 forward of the same params and batch.
        if abs(fused[0] - ref_loss) > 0.05:
            raise AssertionError(f"first fused loss {fused[0]} vs float32 reference {ref_loss}")
        # The steps cycle over the same few batches: the last epoch against the first.
        epoch = len(batches)
        head, tail = float(np.mean(fused[:epoch])), float(np.mean(fused[-epoch:]))
        if not tail < head:
            raise AssertionError(f"fused loss did not fall: first epoch {head}, last {tail}")
        if not float(np.mean(eager)) < head:
            raise AssertionError(f"eager steps lost the fused steps' progress: {eager} vs {head}")
        record.update(
            model=sizes.train_model, batch=sizes.train_batch, seq=128, mixed_precision="bf16",
            fused_steps=sizes.fused_steps, eager_steps=sizes.eager_steps,
            fused_wall_s=round(fused_s, 3), eager_wall_s=round(eager_s, 3),
            float32_reference_loss=round(ref_loss, 5),
            first_fused_loss=round(fused[0], 5),
            fused_loss_first_last_epoch=[round(head, 5), round(tail, 5)],
            fused_losses=[round(x, 5) for x in fused],
            eager_losses=[round(x, 5) for x in eager],
            native_data_plane="c++" if native_available() else "numpy fallback",
            peak_hbm_gb=peak_hbm_gb(),
        )


def phase_train_flash(ledger, sizes: Sizes, seed: int) -> None:
    """llama at seq >= 1024: the trainer's one TPU-only dispatch branch (auto-flash),
    under remat "dots" with bf16 params and moments."""
    import jax
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import create_named_model, get_model_family
    from accelerate_tpu.ops import attention
    from accelerate_tpu.parallel.sharding import data_spec
    from accelerate_tpu.utils import CompilationConfig, FullyShardedDataParallelPlugin, set_seed
    from jax.sharding import NamedSharding

    on_tpu = jax.devices()[0].platform == "tpu"
    with phase("train-flash", ledger) as record:
        set_seed(seed)
        _family, cfg = get_model_family(sizes.flash_model)
        model = create_named_model(
            sizes.flash_model, seq_len=sizes.flash_seq, rng=jax.random.key(seed),
            param_dtype="bfloat16",
        )
        accelerator = Accelerator(
            mixed_precision="bf16",
            compilation_config=CompilationConfig(remat_policy="dots"),
            fsdp_plugin=FullyShardedDataParallelPlugin(param_dtype="bfloat16"),
        )
        pmodel, popt = accelerator.prepare(model, optax.adamw(1e-4))
        model.params = None  # prepare() copied them; 16 GB has no room for both
        del model
        rng = np.random.default_rng(seed)
        sharding = NamedSharding(accelerator.mesh, data_spec(accelerator.mesh))
        batch = jax.device_put(
            {"input_ids": rng.integers(
                1, cfg.vocab_size, (sizes.flash_batch, sizes.flash_seq)).astype(np.int32)},
            sharding,
        )
        attention.LAST_DISPATCH = None
        step_fn = accelerator.train_step()
        losses = [step_fn(batch) for _ in range(sizes.flash_steps)]
        jax.block_until_ready((losses, pmodel.params))
        losses = [float(x) for x in jax.device_get(losses)]
        dispatch = attention.LAST_DISPATCH
        if on_tpu and dispatch != "flash":
            raise AssertionError(f"seq {sizes.flash_seq} on TPU dispatched {dispatch!r}, not flash")
        if not np.all(np.isfinite(losses)):
            raise AssertionError(f"non-finite loss: {losses}")
        record.update(
            model=sizes.flash_model, batch=sizes.flash_batch, seq=sizes.flash_seq,
            remat_policy="dots", param_dtype="bfloat16", steps=sizes.flash_steps,
            last_dispatch=dispatch, losses=[round(x, 5) for x in losses],
            peak_hbm_gb=peak_hbm_gb(),
        )


# ------------------------------------------------------------------- --chips 4
def phase_multichip(ledger, sizes: Sizes, seed: int, chips: int) -> None:
    """What exists only across chips: a tp=N engine against tp=1, DP/ZeRO training
    against one device, and N in-process replicas each on its own device."""
    import jax
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import create_named_model, get_model_family
    from accelerate_tpu.parallel.mesh import build_mesh
    from accelerate_tpu.parallel.sharding import data_spec, tree_device_nbytes
    from accelerate_tpu.router import Router
    from accelerate_tpu.utils import ParallelismConfig, set_seed
    from jax.sharding import NamedSharding

    devices = jax.devices()[:chips]
    with phase("tp-serve", ledger) as record:
        model, cfg, max_length = build_serve_model(sizes, seed)
        requests = make_requests(sizes, cfg.vocab_size, seed + 2)
        ctx = {"model": model, "max_length": max_length, "requests": requests}
        one, _ = kernel_engine_tokens(ctx, sizes, False)
        router = make_router(model, sizes, max_length, tp=chips)
        engine = router.replica_set.replicas[0].engine
        report = engine.tp_sharding_report()
        weight_bytes = [tree_device_nbytes(engine.params, d) for d in devices]
        kv_bytes = [tree_device_nbytes(engine._cache, d) for d in devices]
        total_weights = sum(x.size * x.dtype.itemsize
                            for x in jax.tree_util.tree_leaves(engine.params))
        total_kv = engine.kv_cache_nbytes
        many = serve_requests(router, requests)
        router.close()
        ref_rows = Reference(model, max_length).rows(requests, one)
        record.update(
            model=sizes.serve_model, tp=chips,
            vs_tp1=compare_tokens(f"tp={chips} vs tp=1", requests, many, one, ref_rows,
                                  LOGIT_TOL),
            per_chip_weight_bytes=weight_bytes, total_weight_bytes=total_weights,
            per_chip_kv_bytes=kv_bytes, total_kv_bytes=total_kv,
            sharded_param_leaves=sum("model" in spec for spec in report["params"].values()),
            param_leaves=len(report["params"]),
        )
        # Nothing silently replicated or left on device 0: every chip holds about
        # 1/N (norms and the scalars replicate, so allow a third over).
        for name, per_chip, total in (("weights", weight_bytes, total_weights),
                                      ("kv", kv_bytes, total_kv)):
            if max(per_chip) > total / chips * 4 / 3 or min(per_chip) < total / chips * 0.9:
                raise AssertionError(f"{name} per chip {per_chip} is not ~1/{chips} of {total}")
        del model, router, engine, ctx
    release_device_memory()

    with phase("dp-zero-train", ledger) as record:
        _family, cfg = get_model_family(sizes.train_model)
        sizes_one = dataclasses.replace(
            sizes, train_batch=sizes.tp_train_batch, train_examples=sizes.tp_train_batch * 2
        )
        host_batches = mrpc_batches(sizes_one, cfg.vocab_size, seed)
        steps = 5

        def run(mesh_devices, auto: bool):
            release_device_memory()
            set_seed(seed)
            model = create_named_model(sizes.train_model, seq_len=128, rng=jax.random.key(seed))
            if auto:
                model.sharding_rules = "auto"
                pcfg = ParallelismConfig(data=-1, model=2)
            else:
                pcfg = ParallelismConfig(data=1)
            accelerator = Accelerator(parallelism_config=pcfg)
            accelerator.state.set_mesh(build_mesh(pcfg, devices=mesh_devices))
            pmodel, popt = accelerator.prepare(model, optax.adamw(1e-4))
            sharding = NamedSharding(accelerator.mesh, data_spec(accelerator.mesh))
            batches = [jax.device_put(b, sharding) for b in host_batches]
            step_fn = accelerator.train_step()
            losses = [step_fn(batches[i % len(batches)]) for i in range(steps)]
            jax.block_until_ready((losses, pmodel.params))
            return [float(x) for x in jax.device_get(losses)], pmodel, popt, accelerator

        # float32 on both sides, so only the reduction order differs across layouts.
        with jax.default_matmul_precision("float32"):
            one_losses, *_ = run(devices[:1], auto=False)
            many_losses, pmodel, popt, accelerator = run(devices, auto=True)
        drift = max(abs(a - b) for a, b in zip(one_losses, many_losses))
        opt_leaves = [l for l in jax.tree_util.tree_leaves(popt.opt_state)
                      if hasattr(l, "sharding") and getattr(l, "ndim", 0) >= 1]
        moments = {}
        for leaf in opt_leaves:
            spec = str(getattr(leaf.sharding, "spec", "single-device"))
            moments[spec] = moments.get(spec, 0) + leaf.size * leaf.dtype.itemsize
        record.update(
            model=sizes.train_model, mesh=dict(accelerator.mesh.shape),
            global_batch=sizes.tp_train_batch, steps=steps,
            one_device_losses=[round(x, 5) for x in one_losses],
            mesh_losses=[round(x, 5) for x in many_losses],
            max_loss_drift=round(drift, 6),
            optimizer_moment_bytes_by_spec=moments,
            per_chip_opt_bytes=[tree_device_nbytes(popt.opt_state, d) for d in devices],
            per_chip_param_bytes=[tree_device_nbytes(pmodel.params, d) for d in devices],
        )
        if drift > 5e-3:
            raise AssertionError(f"DP/ZeRO losses drift {drift} from one device: "
                                 f"{one_losses} vs {many_losses}")
        if not any("data" in spec for spec in moments):
            raise AssertionError(f"no optimizer moment is sharded over 'data': {moments}")
        del pmodel, popt, accelerator
    release_device_memory()

    with phase("replicas", ledger) as record:
        model, cfg, max_length = build_serve_model(sizes, seed)
        requests = make_requests(sizes, cfg.vocab_size, seed + 2)
        router = Router(
            model, replicas=chips, tp=1, num_slots=sizes.num_slots, max_length=max_length,
            chunk_size=sizes.chunk_size,
        )
        serve_requests(router, requests)
        placement = {}
        for replica in router.replica_set.replicas:
            engine = replica.engine
            param_devs = sorted({str(d) for x in jax.tree_util.tree_leaves(engine.params)
                                 for d in x.devices()})
            cache_devs = sorted({str(d) for x in jax.tree_util.tree_leaves(engine._cache)
                                 for d in x.devices()})
            placement[replica.index] = {
                "params": param_devs, "cache": cache_devs,
                "requests_served": int(engine.stats["inserts"]),
            }
        router.close()
        record.update(model=sizes.serve_model, replicas=chips, placement=placement)
        homes = [tuple(p["params"]) for p in placement.values()]
        if any(p["params"] != p["cache"] or len(p["params"]) != 1 for p in placement.values()):
            raise AssertionError(f"a replica's params and cache are not on one device: {placement}")
        if len(set(homes)) != min(chips, len(devices)):
            raise AssertionError(f"replicas share a device: {placement}")
        del model, router
    release_device_memory()


# --------------------------------------------------------------------------- main
def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4),
                        help="4 runs ONLY the multi-chip phase and what it is compared with")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax  # the first thing that touches the chip is the device phase below

    from accelerate_tpu.utils.environment import configure_compile_cache

    cache_dir = configure_compile_cache()
    ledger = CompileLedger()
    info = phase_device(ledger, cache_dir, args.chips)
    if args.chips == 1:
        ctx: dict = {}
        phase_serve(ledger, SMOKE, args.seed, ctx)
        phase_serve_kernel(ledger, SMOKE, ctx)
        ctx.clear()
        release_device_memory()
        phase_train(ledger, SMOKE, args.seed)
        release_device_memory()
        phase_train_flash(ledger, SMOKE, args.seed)
        release_device_memory()
    else:
        phase_multichip(ledger, SMOKE, args.seed, args.chips)
    emit({"ok": True, "device": {"platform": info["platform"], "kind": info["kind"],
                                 "count": jax.device_count()}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
