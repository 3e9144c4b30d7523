"""The hybrid linear-attention family (`models/olmo_hybrid.py`, `ops/delta_rule.py`)
on the serving path, at a tiny size (two periods of the layer pattern, small
heads) with seeded weights, held against the LOGITS of the benchmark's plain
float32 reference (`chipbench/reference/olmo_hybrid.py`: the recurrence token by
token, the convolution a plain sum over its taps, no cache):

  (a) `gated_delta_chunked` equals the token-by-token recurrence for lengths
      that are and are not multiples of the chunk, with padding after the real
      length and `beta > 1`; `gated_delta_step` (both implementations)
      continues it; the convolution keeps the last REAL inputs;
  (b) prefill then decode through the engine's own programs equals the
      reference's full forward position by position, across a page boundary of
      the full layers and across an insert bucket's padding;
  (c) a slot released and given to a second request serves it as a fresh
      engine would; mixed admissions compile the decode chunk once; the
      counters are on the spans and nothing waits twice;
  (d) a page-only family's engine is what it was: no by-slot leaf, prefix
      cache on, no state counters;
  (e) what is not built is refused by name, and the prefix cache reads
      disabled with its reason; the engine's other precisions and reads serve.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from accelerate_tpu.generation import _operand  # noqa: E402
from accelerate_tpu.ops import delta_rule  # noqa: E402
from accelerate_tpu.serving import ContinuousBatcher, Request  # noqa: E402
from accelerate_tpu.utils.operations import tree_slot_state_nbytes  # noqa: E402
from chipbench import harness  # noqa: E402

PAGE = 8
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
TINY = {
    "family": "olmo_hybrid", "vocab_size": 512, "max_position_embeddings": 256, "hidden_size": 128,
    "intermediate_size": 256, "num_hidden_layers": 8, "layer_types": PERIOD * 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-6, "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 32, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "init": {"std": 0.05},
}


@pytest.fixture(scope="module")
def reference():
    return harness.load_module("reference", "olmo_hybrid")


@pytest.fixture(scope="module")
def model(reference):
    params = reference.init_params(TINY, jax.random.key(11), "float32")
    return harness.load_module("adapters", "olmo_hybrid").build_model(TINY, params, "float32")


@pytest.fixture(autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


# --------------------------------------------------------- (a) the delta rule
def _token_by_token(q, k, v, log_alpha, beta, state):
    """The recurrence as ISSUE 34 writes it, in numpy: [B, T, H, .] operands."""
    q, k, v, log_alpha, beta = (np.asarray(x, np.float64) for x in (q, k, v, log_alpha, beta))
    state = np.asarray(state, np.float64).copy()
    out = np.zeros(v.shape)
    for t in range(q.shape[1]):
        decayed = np.exp(log_alpha[:, t])[..., None, None] * state
        read = np.einsum("bhk,bhkv->bhv", k[:, t], decayed)
        state = decayed + k[:, t][..., :, None] * (beta[:, t][..., None] * (v[:, t] - read))[..., None, :]
        out[:, t] = np.einsum("bhk,bhkv->bhv", q[:, t], state)
    return out, state


def _operands(length, seed, batch=2, heads=3, dk=8, dv=16):
    keys = jax.random.split(jax.random.key(seed), 6)
    q = delta_rule.l2_normalize(jax.random.normal(keys[0], (batch, length, heads, dk))) * dk ** -0.5
    k = delta_rule.l2_normalize(jax.random.normal(keys[1], (batch, length, heads, dk)))
    v = jax.random.normal(keys[2], (batch, length, heads, dv))
    log_alpha = -jnp.exp(2.0 * jax.random.normal(keys[3], (batch, length, heads)))  # alpha from ~0 to ~1
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(keys[4], (batch, length, heads)))  # (0, 2)
    state = jax.random.normal(keys[5], (batch, heads, dk, dv))
    return q, k, v, log_alpha, beta, state


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("length", [1, 5, 16, 63, 64, 65, 128, 130])
def test_chunked_scan_equals_the_token_by_token_recurrence(length, chunk):
    q, k, v, log_alpha, beta, state = _operands(length, seed=length)
    assert float(beta.max()) > 1.0 or length < 3  # the negative-eigenvalue range is exercised
    want_o, want_state = _token_by_token(q, k, v, log_alpha, beta, state)
    got_o, got_state = delta_rule.gated_delta_chunked(q, k, v, log_alpha, beta, state, chunk=chunk)
    np.testing.assert_allclose(got_o, want_o, rtol=0, atol=2e-4)
    np.testing.assert_allclose(got_state, want_state, rtol=0, atol=2e-4)


@pytest.mark.parametrize("length,real", [(8, 3), (32, 20), (64, 64), (128, 65), (128, 1)])
def test_a_buckets_padding_leaves_the_state_as_the_last_real_token_left_it(length, real):
    q, k, v, log_alpha, beta, state = _operands(length, seed=100 + real)
    valid = (jnp.arange(length) < real)[None, :, None]
    got_o, got_state = delta_rule.gated_delta_chunked(
        q, k, v, jnp.where(valid, log_alpha, 0.0), jnp.where(valid, beta, 0.0), state)
    want_o, want_state = _token_by_token(*(x[:, :real] for x in (q, k, v, log_alpha, beta)), state)
    np.testing.assert_allclose(got_state, want_state, rtol=0, atol=2e-4)
    np.testing.assert_allclose(got_o[:, :real], want_o, rtol=0, atol=2e-4)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_one_token_update_continues_the_scan(impl):
    """Ten tokens chunked, then five more one at a time on the slot layout, equal
    fifteen token by token. The kernel runs in the Pallas interpreter here and
    takes q and k in bfloat16, as the model hands them over."""
    heads, dk, dv = 4, 16, 32  # heads * dv = 128: whole lane tiles, as the kernel's blocks need
    q, k, v, log_alpha, beta, state = _operands(15, seed=7, heads=heads, dk=dk, dv=dv)
    if impl == "pallas":
        q, k = (x.astype(jnp.bfloat16).astype(jnp.float32) for x in (q, k))
    want_o, want_state = _token_by_token(q, k, v, log_alpha, beta, state)
    _, after = delta_rule.gated_delta_chunked(*(x[:, :10] for x in (q, k, v, log_alpha, beta)), state)
    rows = delta_rule.to_slot_layout(after)
    assert rows.shape == (2, dk, heads * dv)
    np.testing.assert_array_equal(delta_rule.from_slot_layout(rows, heads), after)
    for t in range(10, 15):
        o, rows = delta_rule.gated_delta_step(q[:, t], k[:, t], v[:, t], jnp.exp(log_alpha[:, t]), beta[:, t],
                                              rows, impl=impl)
        np.testing.assert_allclose(o, want_o[:, t], rtol=0, atol=2e-4)
    np.testing.assert_allclose(delta_rule.from_slot_layout(rows, heads), want_state, rtol=0, atol=2e-4)
    with pytest.raises(ValueError, match="unknown impl"):
        delta_rule.gated_delta_step(q[:, 0], k[:, 0], v[:, 0], beta[:, 0], beta[:, 0], rows, impl="triton")


def test_the_convolution_keeps_the_last_real_inputs():
    keys = jax.random.split(jax.random.key(3), 3)
    x, taps, before = (jax.random.normal(keys[0], (2, 10, 6)), jax.random.normal(keys[1], (4, 6)),
                       jax.random.normal(keys[2], (2, 3, 6)))
    whole = np.concatenate([before, x], axis=1)
    want = sum(whole[:, j:j + 10] * np.asarray(taps)[j] for j in range(4))
    y, after = delta_rule.causal_conv(x, taps, before)
    np.testing.assert_allclose(y, want, atol=1e-6)
    np.testing.assert_array_equal(after, whole[:, -3:])
    # right padding: row 0 has 2 real inputs (one of the old state's survives), row 1 has 7
    valid = jnp.arange(10)[None, :] < jnp.asarray([[2], [7]])
    y, after = delta_rule.causal_conv(x, taps, before, valid)
    np.testing.assert_array_equal(after[0], whole[0, 2:5])
    np.testing.assert_array_equal(after[1], whole[1, 7:10])
    np.testing.assert_allclose(y[1, :7], want[1, :7], atol=1e-6)
    # left padding (a batch of ragged prompts): the pads count as zeros, the state is the block's tail
    valid = jnp.arange(10)[None, :] >= jnp.asarray([[4], [0]])
    y, after = delta_rule.causal_conv(x, taps, jnp.zeros_like(before), valid)
    alone, _ = delta_rule.causal_conv(x[:1, 4:], taps, jnp.zeros_like(before[:1]))
    np.testing.assert_allclose(y[0, 4:], alone[0], atol=1e-6)
    np.testing.assert_array_equal(after[0], x[0, -3:])


# ----------------------------------- (b) prefill, then decode, against the reference
class Pool:
    """The engine's own programs, driven by hand: one prompt a slot into pages
    this test chooses, then teacher-forced steps whose logits are read."""

    def __init__(self, model, slots=3, max_length=64, **engine):
        self.engine = ContinuousBatcher(model, num_slots=slots, max_length=max_length, chunk_size=4,
                                        page_size=PAGE, **engine)
        self.cache = self.engine._cache
        self.table = np.zeros((slots, self.engine.pages_per_slot), np.int32)
        self.step = jax.jit(self.engine._step_raw)

    def insert(self, slot, prompt, pages):
        engine = self.engine
        bucket, _ = engine.plan_admission_bucket(len(prompt), 0, PAGE, engine._padded_length)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : len(prompt)] = prompt
        row = np.zeros((engine.pages_per_slot,), np.int32)
        row[: len(pages)] = pages
        first, self.cache, _, _ = engine._insert_fn(bucket)(
            engine.params, self.cache, None, jnp.asarray(padded), _operand(len(prompt), np.int32),
            _operand(0, np.int32), _operand(0, np.int32), jnp.asarray(row), _operand(slot, np.int32),
            _operand(1.0, np.float32), _operand(1.0, np.float32), engine._rng, engine._new_first_token())
        self.table[slot] = row
        return bucket, int(first[slot])

    def decode(self, slot, token, position):
        """One step with every other slot idle (position 0 of the scratch page)."""
        tokens = np.zeros(self.table.shape[0], np.int32)
        positions = np.zeros(self.table.shape[0], np.int32)
        table = np.zeros_like(self.table)
        tokens[slot], positions[slot], table[slot] = token, position, self.table[slot]
        logits, self.cache = self.step(self.engine.params, self.cache, jnp.asarray(tokens),
                                       jnp.asarray(positions), jnp.asarray(table))
        return np.asarray(logits[slot])


def _want(reference, model, ids):
    return np.asarray(reference.logits(model.params, TINY, jnp.asarray(np.asarray(ids, np.int32)[None, :]))[0])


@pytest.mark.parametrize("prompt_len", [5, 8, 13, 20])
def test_prefill_then_decode_equals_the_references_forward(reference, model, prompt_len):
    """Prompts that end inside a bucket's padding (5 of 8, 13 of 16, 20 of 32)
    and on its edge (8 of 8); the decode crosses page boundaries of the full
    layers (pages of 8 tokens, deliberately not in order) — every step's
    logits against the reference's full forward at that position."""
    rng = np.random.default_rng(prompt_len)
    prompt = rng.integers(1, TINY["vocab_size"], prompt_len).astype(np.int32)
    follow = rng.integers(1, TINY["vocab_size"], 14).astype(np.int32)
    pool = Pool(model)
    bucket, first = pool.insert(1, prompt, pages=[7, 3, 12, 5, 9])
    assert bucket >= prompt_len and (bucket > prompt_len) == (prompt_len not in (8,))
    ids = np.concatenate([prompt, follow])
    want = _want(reference, model, ids)
    assert first == int(np.argmax(want[prompt_len - 1]))
    for j, token in enumerate(follow):
        got = pool.decode(1, token, prompt_len + j)
        np.testing.assert_allclose(got, want[prompt_len + j], rtol=0, atol=3e-4)


def test_two_slots_keep_their_own_state(reference, model):
    """Two requests decoded in the same steps: each slot's logits are its own
    request's, whatever the other holds."""
    rng = np.random.default_rng(5)
    a, b = (rng.integers(1, TINY["vocab_size"], n).astype(np.int32) for n in (11, 6))
    pool = Pool(model)
    pool.insert(0, a, pages=[2, 4])
    pool.insert(2, b, pages=[1, 3])
    step = jax.jit(pool.engine._step_raw)
    follow_a, follow_b = rng.integers(1, TINY["vocab_size"], (2, 4)).astype(np.int32)
    cache = pool.cache
    for j in range(4):
        tokens = np.asarray([follow_a[j], 0, follow_b[j]], np.int32)
        positions = np.asarray([len(a) + j, 0, len(b) + j], np.int32)
        logits, cache = step(pool.engine.params, cache, jnp.asarray(tokens), jnp.asarray(positions),
                             jnp.asarray(pool.table))
        np.testing.assert_allclose(
            logits[0], _want(reference, model, np.concatenate([a, follow_a[: j + 1]]))[-1], rtol=0, atol=3e-4)
        np.testing.assert_allclose(
            logits[2], _want(reference, model, np.concatenate([b, follow_b[: j + 1]]))[-1], rtol=0, atol=3e-4)


# ------------------------------------------------------------ (c) the engine
def _requests(seed, lengths, new_tokens=9):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(1, TINY["vocab_size"], n).astype(np.int32), max_new_tokens=new_tokens + i)
            for i, n in enumerate(lengths)]


def _assert_greedy(reference, model, prompt, tokens, width=96):
    """`tokens` are the reference's greedy continuation of `prompt`: each is the
    argmax at its position of ONE teacher-forced forward (causal, so the right
    padding to a shared width is never seen)."""
    ids = np.zeros(width, np.int32)
    ids[: len(prompt) + len(tokens) - 1] = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    best = np.argmax(_want(reference, model, ids), axis=-1)
    assert list(best[len(prompt) - 1: len(prompt) - 1 + len(tokens)]) == list(tokens)


@pytest.mark.parametrize("backlog", [False, True], ids=["no-backlog", "backlog"])
def test_a_reused_slot_serves_its_second_request_as_a_fresh_engine_would(reference, model, backlog):
    """Six requests through two slots: every slot is released and given to a
    later request, whose state must be its own insert's — the tokens are the
    reference's greedy continuation, and a fresh engine's. Submitted at once
    (a backlog) the engine runs a chunk ahead, and a slot's next tenant writes
    its state behind the chunk that still holds the last one's; submitted as
    slots free up it never does."""
    from accelerate_tpu.telemetry import FlightRecorder, Tracer

    from test_serving import _serve

    tracer = Tracer(recorder=FlightRecorder())
    engine = ContinuousBatcher(model, num_slots=2, max_length=96, chunk_size=4, page_size=PAGE, tracer=tracer)
    requests = _requests(1, (5, 17, 33, 20, 3, 40))
    _serve(engine, requests, backlog)
    served = {rid: r.tokens for rid, r in engine.results.items()}
    assert (engine.stats["chunks_ahead_share"] >= 0.5) is backlog
    assert engine.stats["slot_chunks_lost_to_eos"] == 0
    assert engine.trace_counts["decode_chunk"] == 1  # mixed admissions, one decode program
    assert engine.trace_counts["insert"] == len({1 << (len(r.input_ids) - 1).bit_length() for r in requests})
    assert engine.stats["waits_per_step"] == 1.0
    for request in requests:
        assert len(served[request.request_id]) == request.max_new_tokens
        _assert_greedy(reference, model, request.input_ids, served[request.request_id])
    alone = ContinuousBatcher(model, num_slots=2, max_length=96, chunk_size=4, page_size=PAGE)
    last = requests[-1]
    assert list(alone.run([last])[last.request_id]) == list(served[last.request_id])
    # what the spans carry: the state's bytes off the leaves' shapes, the slots a chunk updates
    state = 6 * (4 * 16 * 32 * 4 + 3 * 256 * 4)  # 6 linear layers: S float32 + 3 inputs of 256 channels, float32 here
    assert engine.stats["state_bytes_per_slot"] == state == tree_slot_state_nbytes(engine._cache)
    assert 0.0 < engine.stats["state_share_of_cache"] < 1.0
    chunks = [r["attrs"] for r in tracer.recorder.records() if r.get("name") == "serve.decode_chunk"]
    assert chunks and all(c["state_bytes_per_slot"] == state and 1 <= c["state_slots"] <= 2 for c in chunks)
    assert all(c["kv_page_bytes"] == PAGE * engine.stats["kv_bytes_per_token"] for c in chunks)
    inserts = [r["attrs"] for r in tracer.recorder.records() if r.get("name") == "serve.insert"]
    assert {(i["bucket"], i["scan_chunks"]) for i in inserts} == {(8, 1), (32, 1), (64, 1), (4, 1)}


def test_the_cache_tree_holds_both_kinds_of_leaf(model):
    engine = ContinuousBatcher(model, num_slots=3, max_length=64, page_size=PAGE)
    shapes = {"/".join(str(getattr(p, "key", p)) for p in path): (leaf.shape, leaf.dtype)
              for path, leaf in jax.tree_util.tree_flatten_with_path(engine._cache)[0]}
    assert shapes["layer_0/mixer/recurrent_state"] == ((3, 16, 4 * 32), jnp.float32)  # by slot
    assert shapes["layer_0/mixer/conv_state"] == ((3, 3, 4 * (16 + 16 + 32)), jnp.float32)
    # every fourth layer: pages, 16 stored heads for the model's 4 (whole tiles)
    assert shapes["layer_3/mixer/cached_key"] == ((engine.num_pages, PAGE, 16, 32), jnp.float32)
    assert sorted(k.split("/")[0] for k in shapes if k.endswith("cached_key")) == ["layer_3", "layer_7"]
    assert sum(k.endswith("recurrent_state") for k in shapes) == 6
    assert engine.stats["kv_bytes_per_token"] == 2 * 2 * 16 * 32 * 4


# ------------------------------------------- (d) a page-only family is what it was
def test_a_page_only_family_keeps_no_state_and_its_prefix_cache():
    from accelerate_tpu.models import create_gpt_neox_model, gpt_neox_tiny
    from accelerate_tpu.telemetry import FlightRecorder, Tracer

    tracer = Tracer(recorder=FlightRecorder())
    engine = ContinuousBatcher(create_gpt_neox_model(gpt_neox_tiny()), num_slots=2, max_length=64, chunk_size=4,
                               tracer=tracer)
    names = {str(getattr(path[-1], "key", path[-1]))
             for path, _ in jax.tree_util.tree_flatten_with_path(engine._cache)[0]}
    assert names == {"cached_key", "cached_value"}
    assert tree_slot_state_nbytes(engine._cache) == 0
    assert engine.stats["prefix_cache"]["enabled"] is True
    assert engine.stats["prefix_cache"]["disabled_reason"] is None
    assert "state_bytes_per_slot" not in engine.stats
    engine.run(_requests(2, (20, 20)))
    for record in tracer.recorder.records():
        if record.get("name") in ("serve.decode_chunk", "serve.insert"):
            assert not {"state_slots", "state_bytes_per_slot", "kv_page_bytes", "scan_chunks"} & set(record["attrs"])
    # the insert is told of no padding: its program takes no mark of real positions
    lowered = engine._insert_fn(32).lower(
        engine.params, engine._cache, None, jnp.zeros((1, 32), jnp.int32), _operand(20, np.int32),
        _operand(0, np.int32), _operand(0, np.int32), jnp.zeros((engine.pages_per_slot,), jnp.int32),
        _operand(0, np.int32), _operand(1.0, np.float32), _operand(1.0, np.float32), engine._rng,
        engine._new_first_token())
    assert "pad_mask" not in lowered.as_text() and "i1[1,32]" not in lowered.as_text().replace("xi1", "i1")


# ------------------------------------------------ (e) refusals, and what serves
@pytest.mark.parametrize("argument,names", [
    ({"speculative": True}, "speculative verify with a state roll-back"),
    ({"tp": 2}, "tensor-parallel layout for by-slot state"),
])
def test_recurrent_state_refuses_what_is_not_built_and_names_it(model, argument, names):
    with pytest.raises(ValueError, match=names):
        ContinuousBatcher(model, num_slots=2, max_length=64, **argument)


def test_the_prefix_cache_reads_disabled_with_its_reason(model):
    engine = ContinuousBatcher(model, num_slots=2, max_length=64)
    view = engine.stats["prefix_cache"]
    assert view["enabled"] is False and "state at that boundary" in view["disabled_reason"]
    shared = np.arange(1, 41, dtype=np.int32)
    engine.run([Request(0, shared, max_new_tokens=3), Request(1, shared, max_new_tokens=3)])
    assert engine.stats["prefix_cache"]["hits"] == 0 and engine.stats["prefix_cache"]["prefill_tokens_saved"] == 0
    assert list(engine.results[0].tokens) == list(engine.results[1].tokens)
    off = ContinuousBatcher(model, num_slots=2, max_length=64, prefix_cache=False)
    assert off.stats["prefix_cache"]["disabled_reason"] == "prefix_cache=False"


@pytest.mark.parametrize("engine_args,same_tokens", [
    ({"attention_impl": "pallas_paged"}, True),
    ({"kv_cache_dtype": "int8"}, None),  # a quantized pool for the full layers: serves, rounding may move a token
    ({"weight_dtype": "int8"}, None),
    ({"use_repetition_penalty": True}, True),  # penalty 1.0 a request: the plain greedy tokens
], ids=["pallas_paged", "kv_int8", "weights_int8", "penalty"])
def test_the_engines_other_reads_and_precisions_serve_the_family(model, engine_args, same_tokens):
    requests = _requests(3, (12, 30, 7))
    plain = ContinuousBatcher(model, num_slots=2, max_length=64, chunk_size=4).run(requests)
    engine = ContinuousBatcher(model, num_slots=2, max_length=64, chunk_size=4, **engine_args)
    served = engine.run(requests)
    assert all(r.finish_reason == "length" for r in engine.results.values())
    assert [len(served[r.request_id]) for r in requests] == [r.max_new_tokens for r in requests]
    if same_tokens:
        assert all(list(served[r.request_id]) == list(plain[r.request_id]) for r in requests)
    if engine_args.get("weight_dtype") == "int8":
        mixer = engine.params["params"]["layer_0"]["mixer"]
        # the five projections and the two gates are int8; what the layer multiplies by hand is not
        assert all(set(mixer[name]["kernel"]) == {"q", "scale"} for name in ("wq", "wk", "wv", "wg", "wo", "wa", "wb"))
        assert mixer["conv_weight"].dtype == jnp.float32 and mixer["A_log"].dtype == jnp.float32
        full = engine.params["params"]["layer_3"]
        assert all(set(full["mixer"][name]["kernel"]) == {"q", "scale"} for name in ("wq", "wk", "wv", "wo"))
        assert all(set(full["mlp"][name]["kernel"]) == {"q", "scale"} for name in ("w_gate", "w_up", "w_down"))


def test_a_verify_block_over_recurrent_state_is_refused_where_it_is_traced(model):
    engine = ContinuousBatcher(model, num_slots=2, max_length=64)
    block = jnp.zeros((2, 3), jnp.int32)
    with pytest.raises(ValueError, match="no roll-back is built"):
        jax.eval_shape(engine._verify_raw, engine.params, engine._cache, block, block,
                       jnp.zeros((2, engine.pages_per_slot), jnp.int32))


def test_generator_batches_ragged_prompts_through_the_dense_cache(reference, model):
    """`Generator`'s left-padded batch: the pads leave both states alone."""
    from accelerate_tpu.generation import generate

    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, TINY["vocab_size"], n).astype(np.int32) for n in (9, 4)]
    width = max(len(p) for p in prompts)
    ids = np.zeros((2, width), np.int32)
    mask = np.zeros((2, width), np.int32)
    for row, prompt in enumerate(prompts):
        ids[row, width - len(prompt):], mask[row, width - len(prompt):] = prompt, 1
    out = np.asarray(generate(model, jnp.asarray(ids), max_new_tokens=6, attention_mask=jnp.asarray(mask)))
    for row, prompt in enumerate(prompts):
        _assert_greedy(reference, model, prompt, out[row, width:])


def test_registry_names_the_family():
    from accelerate_tpu.models import create_named_model, get_model_config
    from accelerate_tpu.models.olmo_hybrid import olmo_hybrid_7b

    assert get_model_config("olmo-hybrid-7b")["linear_value_head_dim"] == 192
    published = olmo_hybrid_7b()
    assert published.layer_types.count("full_attention") == 8 and published.layer_types[3] == "full_attention"
    assert published.decode_cache_kv_heads == 32 and published.linear_conv_channels == 11520
    tiny = create_named_model("olmo-hybrid-tiny")
    logits = tiny.apply_fn(tiny.params, jnp.ones((1, 6), jnp.int32))
    assert logits.shape == (1, 6, 512) and bool(jnp.isfinite(logits).all())
