"""Quantization tests (reference analogue: bnb int8/4-bit loading, utils/bnb.py):
round-trip error bounds, packing size accounting, jit-compatibility of QuantTensor
pytrees, skip rules, and an end-to-end quantized Llama forward close to the dense one."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.utils.quantization import (
    QuantTensor,
    QuantizationConfig,
    dequantize_params,
    load_and_quantize_model,
    quantize_int4,
    quantize_int8,
    quantize_nf4,
    quantize_params,
    quantized_nbytes,
)


def _w(shape, seed=0, scale=0.02):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape).astype(np.float32) * scale)


def test_int8_round_trip():
    w = _w((64, 32))
    q = quantize_int8(w)
    err = np.abs(np.asarray(q.dequantize(jnp.float32)) - np.asarray(w))
    # per-channel absmax/127 bounds the error at half a step
    col_absmax = np.abs(np.asarray(w)).max(axis=0)
    assert (err <= col_absmax / 127.0 * 0.5001 + 1e-8).all()
    assert q.q.dtype == jnp.int8
    assert q.nbytes_quantized < w.size * 4 / 3.5  # ~4x smaller than fp32 (+scales)


@pytest.mark.parametrize("quant", [quantize_int4, quantize_nf4])
def test_4bit_round_trip(quant):
    w = _w((48, 32), seed=1)
    q = quant(w, block_size=64)
    deq = np.asarray(q.dequantize(jnp.float32))
    assert deq.shape == w.shape
    # 4-bit: coarse, but relative error must stay bounded
    rel = np.abs(deq - np.asarray(w)).mean() / np.abs(np.asarray(w)).mean()
    assert rel < 0.2, rel
    # two values per byte + one fp32 scale per 64-block
    expected_bytes = w.size // 2 + (w.size // 64) * 4
    assert q.nbytes_quantized == expected_bytes


def test_4bit_round_trip_with_padding():
    w = _w((5, 7), seed=2)  # 35 elements: forces padding to the 64-block
    for quant in (quantize_int4, quantize_nf4):
        q = quant(w, block_size=64)
        assert q.dequantize(jnp.float32).shape == w.shape


def test_quant_tensor_is_jittable_pytree():
    w = _w((32, 16))
    q = quantize_nf4(w)
    leaves, treedef = jax.tree_util.tree_flatten(q)
    assert len(leaves) == 2  # q + scale only; metadata is static
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.kind == "nf4" and rebuilt.shape == (32, 16)

    @jax.jit
    def matmul(qt, x):
        return x @ qt.dequantize(jnp.bfloat16).astype(jnp.float32)

    out = matmul(q, jnp.ones((4, 32)))
    assert out.shape == (4, 16)


def test_quantize_params_skip_rules():
    params = {"params": {"layer_0": {"kernel": _w((16, 16))}, "lm_head": {"kernel": _w((16, 8))}, "norm": {"scale": _w((16,))}}}
    cfg = QuantizationConfig(load_in_8bit=True, skip_modules=["lm_head"])
    qp = quantize_params(params, cfg)
    assert isinstance(qp["params"]["layer_0"]["kernel"], QuantTensor)
    assert not isinstance(qp["params"]["lm_head"]["kernel"], QuantTensor)  # skipped
    assert not isinstance(qp["params"]["norm"]["scale"], QuantTensor)  # 1-D: kept dense
    deq = dequantize_params(qp, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(deq["params"]["lm_head"]["kernel"]), np.asarray(params["params"]["lm_head"]["kernel"])
    )


def test_quantized_model_end_to_end():
    from accelerate_tpu.models.llama import create_llama_model, llama_tiny

    model = create_llama_model(llama_tiny(), seq_len=16)
    ids = jnp.asarray(np.random.default_rng(0).integers(1, 500, (2, 16)), jnp.int32)
    dense_logits = np.asarray(model.apply_fn(model.params, ids), dtype=np.float32)

    qmodel = load_and_quantize_model(
        model, QuantizationConfig(load_in_8bit=True, compute_dtype=jnp.float32)
    )
    q_logits = np.asarray(jax.jit(qmodel.apply_fn)(qmodel.params, ids), dtype=np.float32)
    assert q_logits.shape == dense_logits.shape
    # int8 per-channel keeps logits close; compare top-1 predictions + numeric drift
    agree = (q_logits.argmax(-1) == dense_logits.argmax(-1)).mean()
    assert agree > 0.9, agree
    drift = np.abs(q_logits - dense_logits).mean() / (np.abs(dense_logits).mean() + 1e-9)
    assert drift < 0.2, drift

    # memory: quantized params must be well under half the dense fp32 footprint
    dense_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(model.params))
    assert quantized_nbytes(qmodel.params) < dense_bytes / 2

    # loss path still works
    loss = qmodel.loss_fn(qmodel.params, {"input_ids": ids}, qmodel.apply_fn)
    loss = loss[0] if isinstance(loss, tuple) else loss
    assert np.isfinite(float(loss))


def test_quantization_config_validation():
    with pytest.raises(ValueError):
        QuantizationConfig(load_in_8bit=True, load_in_4bit=True)
    with pytest.raises(ValueError):
        QuantizationConfig(load_in_4bit=True, quant_type="fp3")
    assert not QuantizationConfig().enabled


def test_quantized_generation_matches_dense_greedy():
    """Generation straight off a quantized bundle (the reference's bnb int8
    serving path): the Generator must dequantize inside its compiled programs.
    Regression: QuantTensor leaves previously hit the raw flax module and raised
    TypeError."""
    from accelerate_tpu.generation import generate
    from accelerate_tpu.models.llama import create_llama_model, llama_tiny

    model = create_llama_model(llama_tiny(), seq_len=32)
    qmodel = load_and_quantize_model(
        model, QuantizationConfig(load_in_8bit=True, compute_dtype=jnp.float32)
    )
    prompt = np.random.default_rng(0).integers(1, 500, (2, 8)).astype(np.int32)
    q_out = np.asarray(generate(qmodel, prompt, max_new_tokens=4))
    dense_out = np.asarray(generate(model, prompt, max_new_tokens=4))
    assert q_out.shape == dense_out.shape
    # compare only the GENERATED suffix (the echoed prompt always matches);
    # int8 per-channel keeps greedy decoding close on a tiny model
    q_gen, dense_gen = q_out[:, 8:], dense_out[:, 8:]
    assert (q_gen == dense_gen).mean() > 0.6, (q_gen, dense_gen)


# ======================================================================
# Serving quantization (ops/quantization.py): int8 weight-only matmuls and
# the int8/fp8 paged KV pool with per-page-per-head scales — round-trip
# bounds, kernel-vs-oracle numerics, engine logit/token budgets, and the
# decode-compiled-once discipline with quantized operands.
# ======================================================================

import dataclasses

from accelerate_tpu.ops.quantization import (
    KV_CACHE_DTYPES,
    WEIGHT_DTYPES,
    dequantize_kv_pages,
    kv_quant_spec,
    quantize_kv_pages,
    quantize_params_int8,
    quantized_pool_write,
    weight_autocast,
)


def _kv_blocks(shape, seed=0, scale=1.0):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape).astype(np.float32) * scale
    )


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8_e4m3"])
def test_kv_page_round_trip_bounds(kv_dtype):
    """Whole-page quantize/dequant (the insert path) stays within the dtype's
    quantization-step bound: int8 within half a step of the per-page-per-head
    scale; fp8 e4m3 within ~2^-4 relative of the page amax (3 mantissa bits)."""
    spec = kv_quant_spec(kv_dtype)
    blocks = _kv_blocks((5, 4, 2, 8), seed=0, scale=0.7)
    q, scales = quantize_kv_pages(blocks, spec)
    assert q.dtype == spec[0] and scales.shape == (5, 2)
    deq = np.asarray(dequantize_kv_pages(q[None], scales[None], jnp.float32))[0]
    err = np.abs(deq - np.asarray(blocks))
    step = np.broadcast_to(np.asarray(scales)[:, None, :, None], err.shape)
    if kv_dtype == "int8":
        assert (err <= step * 0.5001 + 1e-8).all()
    else:
        amax = np.abs(np.asarray(blocks)).max(axis=(1, 3), keepdims=True)
        assert (err <= np.broadcast_to(amax, err.shape) * 0.07 + 1e-8).all()


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8_e4m3"])
def test_quantized_pool_write_maintains_scale_invariant(kv_dtype):
    """The decode write path's invariant: after any sequence of incremental
    token writes — including magnitude GROWTH mid-page, which forces the
    scale-raise + in-dispatch requant — every written row dequantizes back
    within a small multiple of the final page scale (requant adds at most
    half a step per growth event)."""
    spec = kv_quant_spec(kv_dtype)
    num_pages, ps, h, d = 4, 4, 2, 8
    pool = jnp.zeros((num_pages, ps, h, d), spec[0])
    scale = jnp.zeros((num_pages, h), jnp.float32)
    rng = np.random.default_rng(0)
    written = {}
    for t in range(ps):
        x = rng.normal(size=(1, 1, h, d)).astype(np.float32) * (0.1 * (4.0 ** t))
        pid = jnp.asarray([[1]], jnp.int32)
        off = jnp.asarray([[t]], jnp.int32)
        pool, scale = quantized_pool_write(pool, scale, jnp.asarray(x), pid, off, spec)
        written[t] = x[0, 0]
    final_scale = np.asarray(scale)[1]  # [h]
    for t, x in written.items():
        deq = np.asarray(pool[1, t].astype(jnp.float32)) * final_scale[:, None]
        err = np.abs(deq - x)
        if kv_dtype == "int8":
            # ps growth events max: half a step each plus the final half step.
            assert (err <= final_scale[:, None] * (0.5 * (ps + 1)) + 1e-8).all(), (t, err.max())
        else:
            assert (err <= np.abs(x).max() * 0.15 + final_scale[:, None] + 1e-8).all(), (t, err.max())
    # A fresh occupant's offset-0 write RESETS the page scale: stale large
    # scales from a previous request never coarsen the next one.
    small = np.full((1, 1, h, d), 1e-3, np.float32)
    pool, scale = quantized_pool_write(
        pool, scale, jnp.asarray(small), jnp.asarray([[1]], jnp.int32),
        jnp.asarray([[0]], jnp.int32), spec,
    )
    assert (np.asarray(scale)[1] < final_scale + 1e-12).all()
    assert (np.asarray(scale)[1] <= 1e-3 / spec[1] + 1e-9).all()


@pytest.mark.kernels
@pytest.mark.parametrize("kv_dtype", ["int8", "fp8_e4m3"])
def test_paged_kernels_match_dequant_oracle(kv_dtype):
    """The fused-dequant Pallas kernels (interpret mode) against the
    dequantize-then-attend XLA oracle on the SAME quantized pool: decode and
    block-verify outputs must match to float tolerance — the dequant moved
    inside the page-streaming loop, not the math."""
    from accelerate_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_verify_attention,
    )

    rng = np.random.default_rng(1)
    B, Hq, Hkv, D, ps, P, NP = 2, 4, 2, 8, 4, 3, 8
    spec = kv_quant_spec(kv_dtype)
    kq, ks = quantize_kv_pages(_kv_blocks((NP, ps, Hkv, D), 1), spec)
    vq, vs = quantize_kv_pages(_kv_blocks((NP, ps, Hkv, D), 2), spec)
    tbl = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    kd = np.asarray(dequantize_kv_pages(kq[None], ks[None], jnp.float32))[0]
    vd = np.asarray(dequantize_kv_pages(vq[None], vs[None], jnp.float32))[0]
    karr = kd[np.asarray(tbl)].reshape(B, P * ps, Hkv, D)
    varr = vd[np.asarray(tbl)].reshape(B, P * ps, Hkv, D)

    def oracle(qarr, positions):
        s_blk = qarr.shape[1]
        out = np.zeros(qarr.shape, np.float32)
        for b in range(B):
            for j in range(s_blk):
                for hh in range(Hq):
                    kk, vv = karr[b, :, hh // 2, :], varr[b, :, hh // 2, :]
                    s = (qarr[b, j, hh] @ kk.T) / np.sqrt(D)
                    s = np.where(np.arange(P * ps) <= positions[b, j], s, -1e30)
                    p = np.exp(s - s.max())
                    out[b, j, hh] = (p / p.sum()) @ vv
        return out

    q1 = rng.normal(size=(B, 1, Hq, D)).astype(np.float32)
    pos1 = np.asarray([[9], [5]])
    got = np.asarray(paged_decode_attention(
        jnp.asarray(q1), kq, vq, tbl, jnp.asarray(pos1), k_scale=ks, v_scale=vs
    ))
    np.testing.assert_allclose(got, oracle(q1, pos1), atol=2e-5)

    q3 = rng.normal(size=(B, 3, Hq, D)).astype(np.float32)
    pos3 = np.asarray([[7, 8, 9], [3, 4, 5]])
    got = np.asarray(paged_verify_attention(
        jnp.asarray(q3), kq, vq, tbl, jnp.asarray(pos3), k_scale=ks, v_scale=vs
    ))
    np.testing.assert_allclose(got, oracle(q3, pos3), atol=2e-5)


def _drive_step_logits(model, kv_dtype, tokens, page_size=8):
    """Run the serving STEP program (paged slot cache, one token at a time)
    over a fixed token sequence and return the per-step logits — the
    program-level harness for the decode logit-error budget."""
    import jax

    from accelerate_tpu.generation import make_causal_programs
    from accelerate_tpu.models.llama import LlamaForCausalLM

    B, T = tokens.shape
    P = 4
    cfg = dataclasses.replace(
        model.module.config, decode_cache_length=P * page_size,
        decode_slot_cache=True, decode_page_size=page_size,
        decode_num_pages=B * P + 1, decode_kv_cache_dtype=kv_dtype,
    )
    module = LlamaForCausalLM(cfg)
    resolve = lambda p: p
    _, step, _ = make_causal_programs(
        module, resolve, step_mask_operand=True, verify_block=True
    )
    table = jnp.asarray(
        np.arange(1, B * P + 1, dtype=np.int32).reshape(B, P)
    )
    shapes = jax.eval_shape(
        lambda p: module.apply(
            p, jnp.zeros((B, 1), jnp.int32), table, jnp.zeros((B, 1), jnp.int32),
            mutable=["cache"],
        )[1]["cache"],
        model.params,
    )
    cache = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    step = jax.jit(step, donate_argnums=(1,))
    logits_out = []
    for t in range(T):
        logits, cache = step(
            model.params, cache, jnp.asarray(tokens[:, t]),
            jnp.asarray(np.full(B, t, np.int32)), table,
        )
        logits_out.append(np.asarray(logits, np.float32))
    return np.stack(logits_out, axis=1)  # [B, T, V]


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8_e4m3"])
def test_quantized_decode_logit_error_budget(kv_dtype):
    """The decode logit-error budget at the program level: the same token
    sequence driven through the paged step program on a bf16 (unquantized)
    pool vs the quantized pool. Cache quantization perturbs logits only
    through the attention read — the pinned budget is what the engine-level
    token-agreement tests ride on."""
    from accelerate_tpu.models.llama import create_llama_model, llama_tiny

    model = create_llama_model(llama_tiny(), seq_len=16)
    tokens = np.random.default_rng(0).integers(1, 500, (2, 12)).astype(np.int32)
    base = _drive_step_logits(model, "bf16", tokens)
    quant = _drive_step_logits(model, kv_dtype, tokens)
    max_err = np.abs(base - quant).max()
    # fp8 e4m3 carries 3 mantissa bits vs int8's ~7 significant bits, so its
    # budget is proportionally looser (measured ~0.26 vs ~0.15 at this size).
    budget = 0.25 if kv_dtype == "int8" else 0.45
    assert max_err < budget, f"{kv_dtype} decode logit error {max_err} over budget"
    agree = (base.argmax(-1) == quant.argmax(-1)).mean()
    # Random tiny-model logits are near-flat, so hair-thin argmax margins flip
    # under fp8's coarser steps — the floor tracks the logit budget above.
    floor = 0.9 if kv_dtype == "int8" else 0.8
    assert agree >= floor, f"{kv_dtype} greedy argmax agreement {agree}"


def test_quantized_engine_greedy_token_budget():
    """Engine-level accuracy budget: bf16 vs quantized engines on the same
    greedy workload. The bf16-vs-bf16 path is exact (pinned by
    test_serving.py); quantized paths must keep first tokens exact when only
    the CACHE is quantized (insert logits never read the quantized pool for
    a fresh prompt) and stay within a token-agreement budget overall."""
    from accelerate_tpu.models.llama import create_llama_model, llama_tiny
    from accelerate_tpu.serving import ContinuousBatcher, Request

    model = create_llama_model(llama_tiny(), seq_len=32)
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, 500, (int(rng.integers(3, 20)),)).astype(np.int32)
        for _ in range(6)
    ]

    def run(**kw):
        eng = ContinuousBatcher(
            model, num_slots=3, max_length=64, chunk_size=4, page_size=8,
            max_queue=16, **kw,
        )
        out = eng.run([Request(i, p, max_new_tokens=8) for i, p in enumerate(prompts)])
        return {i: [int(t) for t in out[i]] for i in out}

    base = run()

    def agreement(other):
        pairs = [(x, y) for i in base for x, y in zip(base[i], other[i])]
        return sum(x == y for x, y in pairs) / len(pairs)

    for kv_dtype in ("int8", "fp8_e4m3"):
        quant = run(kv_cache_dtype=kv_dtype)
        assert all(base[i][0] == quant[i][0] for i in base), (
            f"{kv_dtype}: first token must be exact (fresh-prompt insert logits "
            "never read the quantized pool)"
        )
        assert agreement(quant) >= 0.6, kv_dtype
    w8 = run(weight_dtype="int8")
    assert agreement(w8) >= 0.6
    both = run(weight_dtype="int8", kv_cache_dtype="int8")
    assert agreement(both) >= 0.6


def test_quantized_decode_compiled_once_and_guarded():
    """The compiled-once pin with quantized operands: an int8-weights +
    int8-KV engine serves mixed admissions (fresh prompts, prefix-hit waves,
    varied lengths) with the decode chunk traced EXACTLY once, and — after
    warmup — zero recompiles and zero guarded host transfers. Dtypes are
    static config; scales ride the cache pytree as traced operands."""
    from accelerate_tpu.analysis import TraceGuard
    from accelerate_tpu.models.llama import create_llama_model, llama_tiny
    from accelerate_tpu.serving import ContinuousBatcher, Request

    model = create_llama_model(llama_tiny(), seq_len=32)
    rng = np.random.default_rng(1)
    prefix = rng.integers(1, 500, (8,)).astype(np.int32)

    def wave(base_id):
        reqs = []
        for i in range(5):
            tail = rng.integers(1, 500, (int(rng.integers(2, 12)),)).astype(np.int32)
            ids = np.concatenate([prefix, tail]) if i % 2 else tail
            reqs.append(Request(base_id + i, ids, max_new_tokens=6))
        return reqs

    eng = ContinuousBatcher(
        model, num_slots=2, max_length=48, chunk_size=4, page_size=8,
        max_queue=16, weight_dtype="int8", kv_cache_dtype="int8",
    )
    eng.warm_inserts()
    eng.run(wave(0))
    eng.run(wave(100))
    guard = TraceGuard(
        transfer_guard="disallow", on_violation="record", name="quant-decode-pin"
    )
    eng.trace_guard = guard
    with guard:
        eng.run(wave(200))
    assert eng.trace_counts["decode_chunk"] == 1, eng.trace_counts
    assert guard.total_recompiles == 0 and guard.host_transfers == 0, (
        guard.report().summary()
    )
    assert eng.kv_pool_itemsize == 1  # int8 pool really is 1 byte/value


def test_quantized_engine_validation():
    """Config validation: off-set dtypes and the contiguous layout that a
    quantized pool never had fail loudly at construction, and weight quantization is idempotent across
    the params setter (the swap_weights seam re-assigns raw params)."""
    from accelerate_tpu.models.llama import create_llama_model, llama_tiny
    from accelerate_tpu.serving import ContinuousBatcher

    model = create_llama_model(llama_tiny(), seq_len=16)
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        ContinuousBatcher(model, max_queue=4, kv_cache_dtype="int4")
    with pytest.raises(ValueError, match="weight_dtype"):
        ContinuousBatcher(model, max_queue=4, weight_dtype="fp4")
    with pytest.raises(ValueError, match="contiguous per-slot KV layout is gone"):
        ContinuousBatcher(model, max_queue=4, paged=False, kv_cache_dtype="int8")
    assert "int8" in KV_CACHE_DTYPES and "int8" in WEIGHT_DTYPES
    eng = ContinuousBatcher(
        model, max_queue=4, max_length=32, page_size=8, weight_dtype="int8"
    )
    q_once = eng.params
    eng.params = model.params  # the rolling-swap seam: raw params in
    leaf = eng.params["params"]["lm_head"]["kernel"]
    assert isinstance(leaf, dict) and leaf["q"].dtype == jnp.int8
    eng.params = eng.params  # already-quantized trees pass through unchanged
    assert eng.params["params"]["lm_head"]["kernel"]["q"].dtype == jnp.int8
    del q_once


@pytest.mark.router
def test_quantized_fleet_serves_with_zero_recompiles():
    """The fleet half of the discipline pin: a Router over quantized engines
    (int8 weights + int8 KV riding `engine_kwargs`) serves token streams
    identical to a single quantized engine, holds 0 recompiles / 0 guarded
    host transfers across the fleet after warmup, and a rolling
    `swap_weights` with RAW params re-quantizes at the engine's params
    setter without poisoning the compiled programs."""
    from accelerate_tpu.analysis import TraceGuard
    from accelerate_tpu.models.llama import create_llama_model, llama_tiny
    from accelerate_tpu.router import Router
    from accelerate_tpu.serving import ContinuousBatcher, Request

    model = create_llama_model(llama_tiny(), seq_len=32)
    rng = np.random.default_rng(2)
    prompts = [
        rng.integers(1, 500, (int(rng.integers(3, 16)),)).astype(np.int32)
        for _ in range(6)
    ]
    kwargs = dict(
        num_slots=2, max_length=48, chunk_size=4, page_size=8,
        weight_dtype="int8", kv_cache_dtype="int8",
    )
    single = ContinuousBatcher(model, max_queue=16, **kwargs)
    expected = single.run([Request(i, p, max_new_tokens=6) for i, p in enumerate(prompts)])

    router = Router(
        model, replicas=2, max_queue=16, default_deadline_s=60.0, **kwargs
    )
    router.warm_inserts()

    def serve(base_id):
        for i, p in enumerate(prompts):
            router.submit(Request(base_id + i, p, max_new_tokens=6))
        while router.pending:
            router.step()
        out = {i: [int(t) for t in router.results[base_id + i].tokens] for i in range(len(prompts))}
        for i in range(len(prompts)):
            router.release(base_id + i)
        return out

    serve(0)  # warm both replicas' decode chunks
    guard = TraceGuard(
        transfer_guard="disallow", on_violation="record", name="quant-fleet-pin"
    )
    with guard:
        got = serve(100)
    assert guard.total_recompiles == 0 and guard.host_transfers == 0, (
        guard.report().summary()
    )
    for i in range(len(prompts)):
        assert got[i] == [int(t) for t in expected[i]], i
    # Rolling swap with RAW (unquantized) params: the engine params setter
    # must re-quantize, and the warm executables must keep serving.
    router.swap_weights(model.params, wait=True)
    swapped = serve(200)
    for i in range(len(prompts)):
        assert swapped[i] == [int(t) for t in expected[i]], i
    router.close()


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8_e4m3"])
def test_recycled_page_stale_content_never_inflates_insert_scales(kv_dtype):
    """Regression: the paged insert gathers a recycled private page's STALE
    dequantized content into the dense cache; before the quantized
    write-back, `tree_zero_cache_tail` must zero rows past the prompt so a
    prior occupant with much larger K/V magnitudes cannot inflate the
    boundary page's amax scale and coarsen the new request's real rows.
    Reproduced at the seam with controlled magnitudes: stale 100.0-scale
    content beyond a 0.01-scale prompt's rows must leave the round-trip
    within the half-step bound of the VALID rows' own scale — without the
    zeroing, the stored scale is ~10,000x too coarse and the real rows
    round to zero."""
    from accelerate_tpu.utils.operations import tree_zero_cache_tail

    spec = kv_quant_spec(kv_dtype)
    valid_len, page_size = 5, 8
    dense = {"cached_key": jnp.ones((1, 16, 2, 4), jnp.float32) * 100.0}
    small = np.random.default_rng(0).normal(size=(valid_len, 2, 4)).astype(np.float32) * 0.01
    dense["cached_key"] = dense["cached_key"].at[0, :valid_len].set(jnp.asarray(small))

    zeroed = tree_zero_cache_tail(dense, valid_len)
    assert np.abs(np.asarray(zeroed["cached_key"])[0, valid_len:]).max() == 0.0
    np.testing.assert_allclose(np.asarray(zeroed["cached_key"])[0, :valid_len], small)

    # The insert's write-back: whole-page quantization of the zeroed dense
    # blocks. The boundary page's scale must reflect only the valid rows.
    blocks = np.asarray(zeroed["cached_key"])[0].reshape(2, page_size, 2, 4)
    q, scales = quantize_kv_pages(jnp.asarray(blocks), spec)
    deq = np.asarray(dequantize_kv_pages(q[None], scales[None], jnp.float32))[0]
    err = np.abs(deq[0, :valid_len] - small)
    valid_scale = np.abs(small).max(axis=(0, 2)) / spec[1]  # per-head, valid rows only
    assert (np.asarray(scales)[0] <= valid_scale + 1e-12).all(), (
        "boundary-page scale inflated past the valid rows' own amax"
    )
    if kv_dtype == "int8":
        assert (err <= valid_scale[None, :, None] * 0.5001 + 1e-8).all()
    else:
        # fp8 is a relative quantizer: ~2^-4 of the value plus the subnormal
        # floor at this scale — tight only because the scale stayed honest.
        assert (err <= np.abs(small) * 0.07 + valid_scale[None, :, None] * 0.01 + 1e-8).all()
    # Control: WITHOUT the zeroing the stale tail owns the scale (the bug).
    q_bad, scales_bad = quantize_kv_pages(
        jnp.asarray(np.asarray(dense["cached_key"])[0].reshape(2, page_size, 2, 4)), spec
    )
    assert (np.asarray(scales_bad)[0] > valid_scale * 100).all()
