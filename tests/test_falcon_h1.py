"""The parallel state-space and attention family (`models/falcon_h1.py`,
`ops/ssm.py`) on the serving path, at a tiny size (3 blocks, 4 query heads over
2 KV heads, 4 mixer heads in 2 groups) with seeded weights, held against the
LOGITS of the benchmark's plain float32 reference
(`chipbench/reference/falcon_h1.py`: the recurrence token by token, the
convolution a plain sum over its taps, no cache):

  (a) `ssd_chunked` equals the token-by-token recurrence for lengths that are
      and are not multiples of the chunk, with padding after the real length;
      `ssm_step` (both implementations) continues it;
  (b) prefill then decode through the engine's own programs equals the
      reference's full forward position by position, across a page boundary
      and across an insert bucket's padding — EVERY layer holding pages and a
      by-slot state;
  (c) a slot released and given to a second request serves it as a fresh
      engine would; mixed admissions compile the decode chunk once; the
      counters are on the spans and nothing waits twice;
  (d) every published multiplier matters, and each branch alone (mixer,
      attention, MLP) agrees with the reference's;
  (e) the other families' engines are what they were: cache trees, the prefix
      cache, the chunk of `scan_chunks`;
  (f) what is not built is refused by name, and the prefix cache reads
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
from accelerate_tpu.ops import ssm  # noqa: E402
from accelerate_tpu.serving import ContinuousBatcher, Request  # noqa: E402
from accelerate_tpu.utils.operations import tree_slot_state_nbytes  # noqa: E402
from chipbench import harness  # noqa: E402

PAGE = 8
MULTIPLIERS = {
    "embedding_multiplier": 5.656854249492381, "lm_head_multiplier": 0.0078125, "attention_in_multiplier": 0.8,
    "attention_out_multiplier": 0.0375, "key_multiplier": 0.011048543456039804, "ssm_in_multiplier": 0.25,
    "ssm_out_multiplier": 0.08838834764831845,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738],
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
}
TINY = {
    "family": "falcon_h1", "vocab_size": 512, "max_position_embeddings": 256, "hidden_size": 128,
    "intermediate_size": 256, "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "rope_theta": 10000, "rms_norm_eps": 1e-5, "mamba_d_ssm": 128, "mamba_n_heads": 4,
    "mamba_d_head": 32, "mamba_n_groups": 2, "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_chunk_size": 16,
    **MULTIPLIERS,
}
STATE_BYTES = 3 * (4 * 32 * 16 * 4 + 3 * (128 + 2 * 2 * 16) * 4)  # 3 layers: H float32 + 3 inputs of 192 channels, float32 here


@pytest.fixture(scope="module")
def reference():
    return harness.load_module("reference", "falcon_h1")


@pytest.fixture(scope="module")
def model(reference):
    params = reference.init_params(TINY, jax.random.key(11), "float32")
    return harness.load_module("adapters", "falcon_h1").build_model(TINY, params, "float32")


@pytest.fixture(autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


# ------------------------------------------------------ (a) the state-space scan
def _token_by_token(x, dt, a, b_in, c_in, state):
    """The recurrence as ISSUE 38 writes it, in numpy: H_t = exp(dt A) H + dt x B^T, y = H C."""
    x, dt, a, b_in, c_in = (np.asarray(v, np.float64) for v in (x, dt, a, b_in, c_in))
    state = np.asarray(state, np.float64).copy()
    heads, groups = x.shape[2], b_in.shape[2]
    out = np.zeros(x.shape)
    for t in range(x.shape[1]):
        b_t, c_t = (np.repeat(v[:, t], heads // groups, axis=1) for v in (b_in, c_in))
        state = (np.exp(dt[:, t] * a)[..., None, None] * state
                 + (dt[:, t][..., None] * x[:, t])[..., None] * b_t[:, :, None, :])
        out[:, t] = np.einsum("bhpn,bhn->bhp", state, c_t)
    return out, state


def _operands(length, seed, batch=2, heads=4, p=8, groups=2, n=16):
    keys = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(keys[0], (batch, length, heads, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, length, heads)) - 2.0)  # ~0.02 to ~1
    a = -jnp.exp(jax.random.normal(keys[2], (heads,)))
    b_in, c_in = (jax.random.normal(k, (batch, length, groups, n)) for k in keys[3:5])
    state = jax.random.normal(keys[5], (batch, heads, p, n))
    return x, dt, a, b_in, c_in, state


@pytest.mark.parametrize("chunk", [16, 128])
@pytest.mark.parametrize("length", [1, 5, 16, 17, 127, 128, 130, 256])
def test_chunked_scan_equals_the_token_by_token_recurrence(length, chunk):
    operands = _operands(length, seed=length)
    want_y, want_state = _token_by_token(*operands)
    got_y, got_state = ssm.ssd_chunked(*operands, chunk=chunk)
    np.testing.assert_allclose(got_y, want_y, rtol=0, atol=2e-4)
    np.testing.assert_allclose(got_state, want_state, rtol=0, atol=2e-4)


@pytest.mark.parametrize("length,real", [(8, 3), (32, 20), (128, 128), (256, 129), (128, 1), (512, 300)])
def test_a_buckets_padding_leaves_the_state_as_the_last_real_token_left_it(length, real):
    x, dt, a, b_in, c_in, state = _operands(length, seed=100 + real)
    valid = (jnp.arange(length) < real)[None, :, None]
    got_y, got_state = ssm.ssd_chunked(x, jnp.where(valid, dt, 0.0), a, b_in, c_in, state, chunk=128)
    want_y, want_state = _token_by_token(x[:, :real], dt[:, :real], a, b_in[:, :real], c_in[:, :real], state)
    np.testing.assert_allclose(got_state, want_state, rtol=0, atol=2e-4)
    np.testing.assert_allclose(got_y[:, :real], want_y, rtol=0, atol=2e-4)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_one_token_update_continues_the_scan(impl):
    """Ten tokens chunked, then five more one at a time on the slot layout, equal
    fifteen token by token. The kernel runs in the Pallas interpreter here and
    takes B and C in bfloat16, as the model hands them over."""
    heads, p, groups, n = 4, 64, 2, 16  # heads / groups * p = 128: whole lane tiles, as the kernel's blocks need
    x, dt, a, b_in, c_in, state = _operands(15, seed=7, heads=heads, p=p, groups=groups, n=n)
    if impl == "pallas":
        b_in, c_in = (v.astype(jnp.bfloat16).astype(jnp.float32) for v in (b_in, c_in))
    want_y, want_state = _token_by_token(x, dt, a, b_in, c_in, state)
    _, after = ssm.ssd_chunked(x[:, :10], dt[:, :10], a, b_in[:, :10], c_in[:, :10], state)
    rows = ssm.to_slot_layout(after)
    assert rows.shape == (2, n, heads * p)  # the slot axis third from the back
    np.testing.assert_array_equal(ssm.from_slot_layout(rows, heads), after)
    for t in range(10, 15):
        y, rows = ssm.ssm_step(x[:, t], dt[:, t], a, b_in[:, t], c_in[:, t], rows, impl=impl)
        np.testing.assert_allclose(y, want_y[:, t], rtol=0, atol=2e-4)
    np.testing.assert_allclose(ssm.from_slot_layout(rows, heads), want_state, rtol=0, atol=2e-4)
    with pytest.raises(ValueError, match="unknown impl"):
        ssm.ssm_step(x[:, 0], dt[:, 0], a, b_in[:, 0], c_in[:, 0], rows, impl="triton")


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

    def decode(self, slots, tokens, positions):
        """One step: `slots` carry `tokens` at `positions`, every other slot idles
        (position 0 of the scratch page)."""
        toks = np.zeros(self.table.shape[0], np.int32)
        pos = np.zeros(self.table.shape[0], np.int32)
        table = np.zeros_like(self.table)
        for slot, token, position in zip(slots, tokens, positions):
            toks[slot], pos[slot], table[slot] = token, position, self.table[slot]
        logits, self.cache = self.step(self.engine.params, self.cache, jnp.asarray(toks), jnp.asarray(pos),
                                       jnp.asarray(table))
        return np.asarray(logits)


def _want(reference, model, ids, config=TINY):
    return np.asarray(reference.logits(model.params, config, jnp.asarray(np.asarray(ids, np.int32)[None, :]))[0])


@pytest.mark.parametrize("prompt_len", [5, 8, 13, 20, 33])
def test_prefill_then_decode_equals_the_references_forward(reference, model, prompt_len):
    """Prompts that end inside a bucket's padding (5 of 8, 13 of 16, 20 of 32, 33
    of 64: two and four chunks of 16, the last ones all padding) and on its edge
    (8 of 8); the decode crosses page boundaries (pages of 8 tokens, deliberately
    not in order) — every step's logits against the reference's full forward at
    that position."""
    rng = np.random.default_rng(prompt_len)
    prompt = rng.integers(1, TINY["vocab_size"], prompt_len).astype(np.int32)
    follow = rng.integers(1, TINY["vocab_size"], 14).astype(np.int32)
    pool = Pool(model)
    bucket, first = pool.insert(1, prompt, pages=[7, 3, 12, 5, 9, 2])
    assert bucket >= prompt_len and (bucket > prompt_len) == (prompt_len not in (8,))
    want = _want(reference, model, np.concatenate([prompt, follow]))
    assert first == int(np.argmax(want[prompt_len - 1]))
    for j, token in enumerate(follow):
        got = pool.decode([1], [token], [prompt_len + j])[1]
        np.testing.assert_allclose(got, want[prompt_len + j], rtol=0, atol=3e-4)


def test_two_slots_keep_their_own_state_and_pages(reference, model):
    """Two requests decoded in the same steps: each slot's logits are its own
    request's, whatever the other holds."""
    rng = np.random.default_rng(5)
    a, b = (rng.integers(1, TINY["vocab_size"], n).astype(np.int32) for n in (11, 6))
    pool = Pool(model)
    pool.insert(0, a, pages=[2, 4])
    pool.insert(2, b, pages=[1, 3])
    follow_a, follow_b = rng.integers(1, TINY["vocab_size"], (2, 4)).astype(np.int32)
    for j in range(4):
        logits = pool.decode([0, 2], [follow_a[j], follow_b[j]], [len(a) + j, len(b) + j])
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
    later request, whose state AND pages must be its own insert's — the tokens
    are the reference's greedy continuation, and a fresh engine's."""
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
    assert engine.stats["device_starved"]["share"] >= 0.0  # the account is kept for this family too
    for request in requests:
        assert len(served[request.request_id]) == request.max_new_tokens
        _assert_greedy(reference, model, request.input_ids, served[request.request_id])
    alone = ContinuousBatcher(model, num_slots=2, max_length=96, chunk_size=4, page_size=PAGE)
    last = requests[-1]
    assert list(alone.run([last])[last.request_id]) == list(served[last.request_id])
    # what the spans carry: the state's bytes off the leaves' shapes, the slots a chunk updates, a layer's pages
    assert engine.stats["state_bytes_per_slot"] == STATE_BYTES == tree_slot_state_nbytes(engine._cache)
    assert 0.0 < engine.stats["state_share_of_cache"] < 1.0
    assert engine.stats["kv_bytes_per_token"] == 3 * 2 * 2 * 32 * 4  # 3 layers x K and V x 2 KV heads of 32, float32
    chunks = [r["attrs"] for r in tracer.recorder.records() if r.get("name") == "serve.decode_chunk"]
    assert chunks and all(c["state_bytes_per_slot"] == STATE_BYTES and 1 <= c["state_slots"] <= 2 for c in chunks)
    assert all(c["kv_page_bytes"] == PAGE * engine.stats["kv_bytes_per_token"] and c["read_impl"] == "xla"
               and c["live_pages"] >= 1 and c["read_blocks"] >= 1 for c in chunks)
    inserts = [r["attrs"] for r in tracer.recorder.records() if r.get("name") == "serve.insert"]
    # chunks of the FAMILY's 16 tokens: a bucket of 64 is four
    assert {(i["bucket"], i["scan_chunks"]) for i in inserts} == {(8, 1), (32, 2), (64, 4), (4, 1)}


def test_every_layers_cache_holds_both_kinds_of_leaf(model):
    engine = ContinuousBatcher(model, num_slots=3, max_length=64, page_size=PAGE)
    shapes = {"/".join(str(getattr(p, "key", p)) for p in path): (leaf.shape, leaf.dtype)
              for path, leaf in jax.tree_util.tree_flatten_with_path(engine._cache)[0]}
    for layer in range(3):
        assert shapes[f"layer_{layer}/mixer/recurrent_state"] == ((3, 16, 4 * 32), jnp.float32)  # by slot
        assert shapes[f"layer_{layer}/mixer/conv_state"] == ((3, 3, 128 + 2 * 2 * 16), jnp.float32)
        # pages of the 2 KV heads, not the 4 query heads
        assert shapes[f"layer_{layer}/attention/cached_key"] == ((engine.num_pages, PAGE, 2, 32), jnp.float32)
        assert shapes[f"layer_{layer}/attention/cached_value"] == ((engine.num_pages, PAGE, 2, 32), jnp.float32)
    assert len(shapes) == 12


# ------------------------------------- (d) the multipliers, and each branch alone
def _without(key, index=None):
    value = 1.0
    if index is not None:
        value = list(TINY[key])
        value[index] = 1.0
    return dict(TINY, **{key: value})


@pytest.mark.parametrize("key,index", [
    ("embedding_multiplier", None), ("lm_head_multiplier", None), ("attention_in_multiplier", None),
    ("attention_out_multiplier", None), ("key_multiplier", None), ("ssm_in_multiplier", None),
    ("ssm_out_multiplier", None), ("ssm_multipliers", 0), ("ssm_multipliers", 1), ("ssm_multipliers", 2),
    ("ssm_multipliers", 3), ("ssm_multipliers", 4), ("mlp_multipliers", 0), ("mlp_multipliers", 1),
])
def test_every_published_multiplier_matters(reference, model, key, index):
    """The reference with ONE multiplier set to 1 (the weights as they are) lies
    far outside the comparison's tolerance of the program's logits, which hold
    to the reference as published: a multiplier left out, or put in the wrong
    place, cannot pass."""
    ids = np.random.default_rng(3).integers(1, TINY["vocab_size"], 24).astype(np.int32)
    got = np.asarray(model.module.apply(model.params, jnp.asarray(ids[None]))[0])
    np.testing.assert_allclose(got, _want(reference, model, ids), rtol=0, atol=3e-4)
    moved = np.abs(_want(reference, model, ids, _without(key, index)) - got).max()
    assert moved > 100 * 3e-4, (key, index, moved)


def test_each_branch_alone_agrees_with_the_references(reference, model):
    """Layer 0's three branches as they are added to the stream — the mixer, the
    attention, the MLP — the program's modules against the reference's
    functions on the same normed input."""
    from accelerate_tpu.models.falcon_h1 import GroupedQueryAttention, Mamba2Mixer, ScaledSwiGLU

    s = reference._Sizes.of(TINY)
    cfg = model.module.config
    ids = np.random.default_rng(4).integers(1, TINY["vocab_size"], (2, 21)).astype(np.int32)
    layer = jax.tree_util.tree_map(jnp.asarray, model.params["params"]["layer_0"])
    x = reference._embed(model.params, ids, s)
    want_mixed, want_attended, want_ffn = reference.branches(layer, x, s)
    u = reference.rms_norm(x, layer["input_norm"]["scale"], s.rms_norm_eps)
    positions = jnp.broadcast_to(jnp.arange(ids.shape[1])[None, :], ids.shape)
    mixed = Mamba2Mixer(cfg).apply({"params": layer["mixer"]}, u * cfg.ssm_in_multiplier, None) * cfg.ssm_out_multiplier
    attended = GroupedQueryAttention(cfg).apply(
        {"params": layer["attention"]}, u * cfg.attention_in_multiplier, positions, None) * cfg.attention_out_multiplier
    after = x + want_mixed + want_attended
    ffn = ScaledSwiGLU(cfg).apply(
        {"params": layer["mlp"]}, reference.rms_norm(after, layer["pre_mlp_norm"]["scale"], s.rms_norm_eps))
    for got, want in ((mixed, want_mixed), (attended, want_attended), (ffn, want_ffn)):
        assert float(jnp.sqrt(jnp.mean(want * want))) > 0.1  # a branch that says something
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


# ------------------------------------------- (e) the other families are what they were
_LEAVES = {
    "gpt-neox-tiny": {"cached_key", "cached_value"},
    "latent-moe-tiny": {"cached_latent", "expert_tokens"},
    "olmo-hybrid-tiny": {"cached_key", "cached_value", "recurrent_state", "conv_state"},
    "falcon-h1-tiny": {"cached_key", "cached_value", "recurrent_state", "conv_state"},
}


@pytest.mark.parametrize("family,scan_chunk", [("gpt-neox-tiny", None), ("latent-moe-tiny", None),
                                               ("olmo-hybrid-tiny", 64), ("falcon-h1-tiny", 16)])
def test_an_engines_cache_tree_and_insert_are_its_familys_own(family, scan_chunk):
    """The leaves an engine's cache holds and the chunk `scan_chunks` counts in:
    a family with no recurrence keeps no state, keeps its prefix cache and
    counts no chunks; a family with one counts chunks of ITS size (64 the delta
    rule's, the state-space family's own `mamba_chunk_size`)."""
    from accelerate_tpu.models import create_named_model
    from accelerate_tpu.telemetry import FlightRecorder, Tracer

    model = create_named_model(family)
    tracer = Tracer(recorder=FlightRecorder())
    engine = ContinuousBatcher(model, num_slots=2, max_length=160, chunk_size=4, tracer=tracer)
    names = {str(getattr(path[-1], "key", path[-1]))
             for path, _ in jax.tree_util.tree_flatten_with_path(engine._cache)[0]}
    assert names == _LEAVES[family]
    recurrent = scan_chunk is not None
    assert bool(tree_slot_state_nbytes(engine._cache)) is recurrent
    assert engine.stats["prefix_cache"]["enabled"] is not recurrent
    assert ("state_bytes_per_slot" in engine.stats) is recurrent
    rng = np.random.default_rng(2)
    vocab = model.module.config.vocab_size
    engine.run([Request(i, rng.integers(1, vocab, n).astype(np.int32), max_new_tokens=3) for i, n in enumerate((20, 100))])
    inserts = [r["attrs"] for r in tracer.recorder.records() if r.get("name") == "serve.insert"]
    assert {i["bucket"] for i in inserts} == {32, 128}
    for attrs in inserts:
        assert attrs.get("scan_chunks") == (-(-attrs["bucket"] // scan_chunk) if recurrent else None)


# ------------------------------------------------ (f) refusals, and what serves
@pytest.mark.parametrize("argument,names", [
    ({"speculative": True}, "FalconH1ForCausalLM: its cache holds recurrent state by slot.*speculative "
                            "verify with a state roll-back"),
    ({"tp": 2}, "FalconH1ForCausalLM: its cache holds recurrent state by slot.*tensor-parallel layout for by-slot state"),
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


@pytest.mark.parametrize("engine_args,same_tokens", [
    ({"attention_impl": "pallas_paged"}, True),
    ({"kv_cache_dtype": "int8"}, None),  # a quantized pool in every layer: serves, rounding may move a token
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
        layer = engine.params["params"]["layer_0"]
        # what the control has to reach: W_in, W_out, the four attention projections, the MLP, the head
        quantized = [layer["mixer"]["w_in"], layer["mixer"]["w_out"], *(layer["attention"][n] for n in ("wq", "wk", "wv", "wo")),
                     *(layer["mlp"][n] for n in ("w_gate", "w_up", "w_down")), engine.params["params"]["lm_head"]]
        assert all(set(entry["kernel"]) == {"q", "scale"} for entry in quantized)
        # what the layer multiplies by hand is not
        assert layer["mixer"]["conv_weight"].dtype == jnp.float32 and layer["mixer"]["A_log"].dtype == jnp.float32


def test_a_verify_block_over_recurrent_state_is_refused_where_it_is_traced(model):
    engine = ContinuousBatcher(model, num_slots=2, max_length=64)
    block = jnp.zeros((2, 3), jnp.int32)
    with pytest.raises(ValueError, match="no roll-back is built"):
        jax.eval_shape(engine._verify_raw, engine.params, engine._cache, block, block,
                       jnp.zeros((2, engine.pages_per_slot), jnp.int32))


def test_generator_batches_ragged_prompts_through_the_dense_cache(reference, model):
    """`Generator`'s left-padded batch: the pads leave both states alone, and
    the rotary positions count real tokens."""
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
    from accelerate_tpu.models.falcon_h1 import falcon_h1_34b

    assert get_model_config("falcon-h1-34b")["mamba_d_state"] == 256
    published = falcon_h1_34b()
    assert published.conv_channels == 5120 and published.in_proj_segments == (4096, 4096, 512, 512, 32)
    assert sum(published.in_proj_segments) == 9248 and published.decode_scan_chunk == 128
    assert (published.num_attention_heads, published.num_key_value_heads, published.head_dim) == (20, 4, 128)
    tiny = create_named_model("falcon-h1-tiny")
    logits = tiny.apply_fn(tiny.params, jnp.ones((1, 6), jnp.int32))
    assert logits.shape == (1, 6, 512) and bool(jnp.isfinite(logits).all())
