"""The latent-attention, sparse-expert family (`models/latent_moe.py`) on the
serving path, at a tiny size with seeded weights, held against the LOGITS of
the benchmark's plain float32 reference (`chipbench/reference/latent_moe.py`:
decompressed attention, every expert on every token, no cache):

  (a) the absorbed decode through the paged pool of latent rows — position by
      position, across a page boundary, after a prefix-cache hit (a suffix
      insert over shared pages), and in a 5-row verify block;
  (b) the dropless expert layer under a deliberately skewed router and a
      choice bias that moves the chosen set but not the weights;
  (c) `ContinuousBatcher` end to end on mixed admissions: decode compiled
      once, tokens equal a dense per-request decode, counters on the spans;
  (d) the three combinations a latent cache refuses, each naming what is
      missing, and the page-walk kernel serving the XLA engine's tokens.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from accelerate_tpu.generation import _operand, generate  # noqa: E402
from accelerate_tpu.models.latent_moe import LatentMoEForCausalLM  # noqa: E402
from accelerate_tpu.serving import ContinuousBatcher, Request  # noqa: E402
from chipbench import harness  # noqa: E402

PAGE = 8
TINY = {
    "family": "latent_moe", "vocab_size": 512, "max_position_embeddings": 256, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32, "num_hidden_layers": 3, "num_attention_heads": 4,
    "n_shared_experts": 2, "n_routed_experts": 8, "num_experts_per_tok": 3, "routed_scaling_factor": 2.446,
    "norm_topk_prob": True, "first_k_dense_replace": 1, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000, "rms_norm_eps": 1e-5,
    "init": {"std": 0.05, "router_bias_std": 0.1},
}


@pytest.fixture(scope="module")
def reference():
    return harness.load_module("reference", "latent_moe")


@pytest.fixture(scope="module")
def model(reference):
    params = reference.init_params(TINY, jax.random.key(11), "float32")
    return harness.load_module("adapters", "latent_moe").build_model(TINY, params, "float32")


@pytest.fixture(params=[1, 2, 4], ids=lambda r: f"run{r}")
def run_pages(request, monkeypatch):
    """The read lists runs of this many pages a slot (`read_run_pages`): the
    tiny model's 4 heads are made a group worth it, at 8-token pages."""
    from accelerate_tpu.ops import attention

    monkeypatch.setattr(attention, "_READ_RUN_MIN_GROUP", 4)
    monkeypatch.setattr(attention, "_READ_RUN_TOKENS", PAGE * request.param)
    monkeypatch.setattr(attention, "_READ_BLOCK_BYTES", 6 * PAGE * 128 * 4)  # blocks of six pages
    assert attention.read_run_pages(PAGE, 4) == request.param
    return request.param


@pytest.fixture(autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


class Pool:
    """The engine's own programs, driven by hand: one prompt a slot into pages
    this test chooses, then teacher-forced steps whose logits are read."""

    def __init__(self, model, slots=3, max_length=64):
        self.engine = ContinuousBatcher(model, num_slots=slots, max_length=max_length, chunk_size=4, page_size=PAGE)
        self.cache = self.engine._cache
        self.table = np.zeros((slots, self.engine.pages_per_slot), np.int32)
        self.step = jax.jit(self.engine._step_raw)
        self.verify = jax.jit(self.engine._verify_raw)

    def insert(self, slot, prompt, pages, matched_pages=0):
        engine, matched_len = self.engine, matched_pages * PAGE
        suffix = prompt[matched_len:]
        bucket, _ = engine.plan_admission_bucket(len(prompt), matched_pages, PAGE, engine._padded_length)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : len(suffix)] = suffix
        row = np.zeros((engine.pages_per_slot,), np.int32)
        row[: len(pages)] = pages
        first, self.cache, _, _ = engine._insert_fn(bucket)(
            engine.params, self.cache, None, jnp.asarray(padded), _operand(len(suffix), np.int32),
            _operand(matched_len, np.int32), _operand(matched_pages, np.int32), jnp.asarray(row),
            _operand(slot, np.int32), _operand(1.0, np.float32), _operand(1.0, np.float32), engine._rng,
            engine._new_first_token())
        self.table[slot] = row
        return int(first[slot])

    def _only(self, slot):
        """The page tables with every other slot idle: at position 0 of the
        scratch page, as the engine parks a slot it is not decoding."""
        table = np.zeros_like(self.table)
        table[slot] = self.table[slot]
        return jnp.asarray(table)

    def decode(self, slot, token, position):
        tokens = np.zeros(self.table.shape[0], np.int32)
        positions = np.zeros(self.table.shape[0], np.int32)
        tokens[slot], positions[slot] = token, position
        logits, self.cache = self.step(self.engine.params, self.cache, jnp.asarray(tokens),
                                       jnp.asarray(positions), self._only(slot))
        return np.asarray(logits[slot])

    def verify_block(self, slot, tokens, first):
        block = np.zeros((self.table.shape[0], len(tokens)), np.int32)
        positions = np.zeros_like(block)
        block[slot], positions[slot] = tokens, first + np.arange(len(tokens))
        logits, self.cache = self.verify(self.engine.params, self.cache, jnp.asarray(block),
                                         jnp.asarray(positions), self._only(slot))
        return np.asarray(logits[slot])


def _want(reference, model, ids):
    return np.asarray(reference.logits(model.params, TINY, jnp.asarray(np.asarray(ids, np.int32)[None, :]))[0])


# ------------------------------------------------ (a) the absorbed paged decode
def test_absorbed_decode_through_the_pool_equals_the_references_forward(reference, model, run_pages):
    rng = np.random.default_rng(0)
    pool = Pool(model)
    prompt_a = rng.integers(1, 512, 21).astype(np.int32)  # two full pages and 5 rows of a third
    forced = rng.integers(1, 512, 14).astype(np.int32)
    first = pool.insert(0, prompt_a, pages=[1, 2, 3, 4, 5])
    want = _want(reference, model, np.concatenate([prompt_a, forced]))
    assert first == int(want[20].argmax())
    # decode positions 21..29: the page boundary at 24 is crossed
    for j in range(9):
        got = pool.decode(0, forced[j], 21 + j)
        np.testing.assert_allclose(got, want[21 + j], atol=2e-5, rtol=0)
    # a 5-row verify block at 30..34: every row after exactly the block's prefix up to it
    got = pool.verify_block(0, forced[9:14], 30)
    np.testing.assert_allclose(got, want[30:35], atol=2e-5, rtol=0)

    # a prefix-cache hit: slot 1 shares slot 0's first two pages and inserts its suffix alone
    prompt_b = np.concatenate([prompt_a[:16], rng.integers(1, 512, 7).astype(np.int32)])
    first_b = pool.insert(1, prompt_b, pages=[1, 2, 6, 7, 8], matched_pages=2)
    want_b = _want(reference, model, np.concatenate([prompt_b, forced]))
    assert first_b == int(want_b[22].argmax())
    for j in range(4):  # 23, then over the boundary at 24
        np.testing.assert_allclose(pool.decode(1, forced[j], 23 + j), want_b[23 + j], atol=2e-5, rtol=0)
    # the shared pages were not rewritten: slot 0 still reads what it wrote
    np.testing.assert_allclose(pool.decode(0, forced[9], 30), want[30], atol=2e-5, rtol=0)


def test_the_pool_holds_one_padded_latent_row_a_token_and_no_value_pool(model):
    engine = ContinuousBatcher(model, num_slots=2, max_length=32, page_size=PAGE)
    leaves = {"/".join(str(getattr(k, "key", k)) for k in path): leaf
              for path, leaf in jax.tree_util.tree_flatten_with_path(engine._cache)[0]}
    pools = {k: v for k, v in leaves.items() if "cached" in k}
    assert sorted(k.split("/")[-1] for k in pools) == ["cached_latent"] * 3
    # [pages + scratch, page_size, the row [c 32 | k_pe 8] in whole 128-lane tiles]
    assert {v.shape for v in pools.values()} == {(2 * 4 + 1, PAGE, 128)}
    assert engine.kv_row_values == 128 and engine.stats["kv_bytes_per_token"] == 3 * 128 * 4


# ----------------------------------------------------- (b) the dropless experts
def test_dropless_experts_equal_the_every_expert_loop_under_a_skewed_router(reference):
    from accelerate_tpu.parallel.expert import dropless_expert_ffn, sigmoid_top_k_routing

    sizes = reference._Sizes.of(TINY)
    tokens, hidden, experts, top_k = 96, TINY["hidden_size"], 8, 3
    layer = reference.init_params(TINY, jax.random.key(5), "float32")["params"]["layer_1"]["moe"]
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(tokens, hidden)), jnp.float32)
    # the skew: expert 0's logit is far above for every token, experts 6 and 7 far below
    kernel = np.asarray(layer["router"]["kernel"]).copy() * 0.1
    logits = np.asarray(x) @ kernel
    offset = np.zeros(experts, np.float32)
    offset[0], offset[6], offset[7] = 6.0, -9.0, -9.0
    # ... made by a rank-one term along a direction every token shares
    shared = np.ones(hidden, np.float32) / hidden
    x = x + 1.0 - jnp.mean(x, axis=1, keepdims=True)  # every token's mean is 1
    kernel = kernel + np.outer(shared * hidden, offset) / hidden
    bias = np.array([0.0, 0.3, -0.3, 0.2, -0.2, 0.1, 0.0, 0.0], np.float32)
    p = dict(layer, router={"kernel": jnp.asarray(kernel)}, router_bias=jnp.asarray(bias))

    want, _margin = reference.experts(p, x, sizes)
    routed_logits = jnp.dot(x, p["router"]["kernel"], precision="highest")
    ids, weights = sigmoid_top_k_routing(routed_logits, p["router_bias"], top_k, TINY["routed_scaling_factor"])
    stacks = p["experts"]
    got, counts = dropless_expert_ffn(x, ids, weights, stacks["w_gate"]["kernel"], stacks["w_up"]["kernel"],
                                      stacks["w_down"]["kernel"])
    got = got + reference.swiglu(x, p["shared"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=0)
    counts = np.asarray(counts)
    assert counts.sum() == tokens * top_k and counts[0] == tokens  # expert 0 takes every token: nothing dropped
    assert counts[6] == 0 and counts[7] == 0  # and some take none
    # the bias moves the chosen set ...
    ids_unbiased, weights_unbiased = sigmoid_top_k_routing(routed_logits, jnp.zeros(experts), top_k,
                                                           TINY["routed_scaling_factor"])
    moved = np.asarray(ids).tolist() != np.asarray(ids_unbiased).tolist()
    assert moved
    # ... but not the weights: where both choose the same experts the weights are equal
    same = np.all(np.sort(np.asarray(ids), 1) == np.sort(np.asarray(ids_unbiased), 1), axis=1)
    assert same.any() and not same.all()
    np.testing.assert_allclose(np.sort(np.asarray(weights)[same], 1), np.sort(np.asarray(weights_unbiased)[same], 1),
                               rtol=1e-6)
    scores = np.asarray(jax.nn.sigmoid(routed_logits))
    chosen = np.take_along_axis(scores, np.asarray(ids), 1)
    np.testing.assert_allclose(np.asarray(weights), chosen / chosen.sum(1, keepdims=True) * 2.446, rtol=1e-5)


def test_the_pallas_grouped_matmul_equals_ragged_dot_with_rows_short_of_a_tile():
    """`parallel.expert._gmm` (what `grouped_matmul` runs on a TPU) in the
    interpreter: rows padded to whole tiles of 128, an empty group, a group
    that crosses a tile."""
    from accelerate_tpu.parallel.expert import _gmm

    rng = np.random.default_rng(6)
    rows = jnp.asarray(rng.normal(size=(300, 64)), jnp.float32)
    kernels = jnp.asarray(rng.normal(size=(5, 64, 32)), jnp.float32)
    sizes = jnp.asarray([100, 0, 57, 140, 3], jnp.int32)
    want = jax.lax.ragged_dot(rows, kernels, sizes)
    got = _gmm(rows, kernels, sizes, interpret=True)
    assert got.shape == (300, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-5)


# ------------------------------------------------------- (c) the engine, whole
def test_engine_on_mixed_admissions_compiles_decode_once_and_matches_dense_decode(model, run_pages):
    from accelerate_tpu.telemetry import FlightRecorder, Tracer

    tracer = Tracer(recorder=FlightRecorder())
    engine = ContinuousBatcher(model, num_slots=4, max_length=96, chunk_size=4, page_size=PAGE, tracer=tracer)
    rng = np.random.default_rng(3)
    shared = rng.integers(1, 512, 24)
    prompts = [rng.integers(1, 512, n) for n in (5, 17, 30, 9, 40, 3)]
    prompts += [np.concatenate([shared, rng.integers(1, 512, n)]) for n in (4, 11)]  # prefix-cache hits
    budgets = (10, 7, 12, 20, 5, 9, 8, 6)
    out = engine.run([Request(i, p, max_new_tokens=m) for i, (p, m) in enumerate(zip(prompts, budgets))])
    assert engine.trace_counts["decode_chunk"] == 1
    assert engine.stats["prefix_cache"]["hits"] >= 3
    for i, (prompt, budget) in enumerate(zip(prompts, budgets)):
        dense = np.asarray(generate(model, np.asarray(prompt, np.int32)[None, :], max_new_tokens=budget))[0]
        np.testing.assert_array_equal(out[i], dense[len(prompt): len(prompt) + budget])
    # what the chunk and the insert say of themselves
    assert engine.stats["kv_bytes_per_token"] == 3 * 128 * 4 and engine.stats["expert_load_max_over_mean"] >= 1.0
    records = tracer.recorder.records()
    chunks = [r["attrs"] for r in records if r.get("kind") == "span" and r["name"] == "serve.decode_chunk"]
    inserts = [r["attrs"] for r in records if r.get("kind") == "span" and r["name"] == "serve.insert"]
    assert chunks and all(c["kv_row_values"] == 128 for c in chunks)
    assert all(c["read_blocks"] >= 1 for c in chunks)
    # the trip count the engine reports is the read's own (`ops.attention.read_blocks`), which lists runs:
    # entries of `run_pages` pages a slot (an idle slot one), in blocks of 6 pages
    from accelerate_tpu.ops import attention

    top = np.asarray([0, 17, 40, 95])
    entries = int((top // (PAGE * run_pages) + 1).sum())
    assert attention.read_blocks(top, *engine._read_shape) == -(-entries // (6 // run_pages))
    for c in chunks:
        # 4 slots x 4 steps x 3 picks over 8 experts, two expert layers: the mean is exact
        assert c["expert_tokens_mean"] == pytest.approx(4 * 4 * 3 / 8)
        assert c["expert_tokens_max"] >= c["expert_tokens_mean"] and 0 < c["experts_touched"] <= 8
    assert all(i["routed_pairs"] == i["bucket"] * 3 * 2 for i in inserts)


def test_int8_weights_reach_the_absorbed_projection_and_the_expert_stacks(model):
    engine = ContinuousBatcher(model, num_slots=2, max_length=48, chunk_size=4, page_size=PAGE, weight_dtype="int8")
    layer = engine.params["params"]["layer_1"]
    for entry in (layer["attention"]["wkv_b"]["kernel"], layer["moe"]["experts"]["w_gate"]["kernel"],
                  layer["moe"]["experts"]["w_down"]["kernel"], layer["moe"]["shared"]["w_up"]["kernel"]):
        assert set(entry) == {"q", "scale"} and entry["q"].dtype == jnp.int8
    rng = np.random.default_rng(4)
    out = engine.run([Request(0, rng.integers(1, 512, 12), max_new_tokens=6)])
    assert len(out[0]) == 6 and engine.stats["finish_reasons"]["length"] == 1


# ------------------------------------------------------------ (d) the refusals
@pytest.mark.parametrize("argument,names", [
    # the tiny model computes in float32: a page of 4 rows is half a tile, which the kernel cannot cut out of the pool
    ({"attention_impl": "pallas_paged", "page_size": 4}, "pages of 4 rows of 128 values.*latent rows are never staged"),
    ({"kv_cache_dtype": "int8"}, "quantized pool for latent rows is not built"),
    ({"tp": 2}, "layout is not built"),
])
def test_a_latent_cache_refuses_what_is_not_built_and_names_it(model, argument, names):
    with pytest.raises(ValueError, match=names):
        ContinuousBatcher(model, **{"num_slots": 2, "max_length": 32, "page_size": PAGE, **argument})


@pytest.mark.parametrize("speculative", [False, True], ids=["plain", "speculative"])
def test_the_page_walk_kernel_serves_the_xla_engines_tokens(model, monkeypatch, speculative):
    """A named `"pallas_paged"` on a latent cache the kernel reads in place
    (interpreted here): the tokens of the `"xla"` engine, request for request,
    plain and with verify blocks of 4 rows a slot, one decode program, the
    chunk span saying which read it holds and counting the kernel's entries —
    a slot's 12 pages walked in runs of 4, the products in pieces of 2."""
    from accelerate_tpu.ops import attention
    from accelerate_tpu.telemetry import FlightRecorder, Tracer

    monkeypatch.setattr(attention, "_KERNEL_RUN_BYTES", 2 * PAGE * 128 * 4)  # a latent run is a K run and a V run
    monkeypatch.setattr(attention, "_KERNEL_PIECE_TOKENS", 2 * PAGE)
    rng = np.random.default_rng(5)
    motif = rng.integers(1, 512, 6)
    prompts = [np.tile(motif, 6)[: int(n)] for n in (9, 30, 21)] + [rng.integers(1, 512, n) for n in (5, 44, 17)]
    mode = dict(speculative=True, draft_tokens=3) if speculative else {}
    tokens = {}
    for impl in ("xla", "pallas_paged"):
        recorder = FlightRecorder()
        engine = ContinuousBatcher(model, num_slots=3, max_length=96, chunk_size=4, page_size=PAGE,
                                   attention_impl=impl, tracer=Tracer(recorder=recorder), **mode)
        assert engine.attention_impl == impl and engine.stats["attention_impl"] == impl
        out = engine.run([Request(i, p, max_new_tokens=14) for i, p in enumerate(prompts)])
        tokens[impl] = {rid: list(map(int, toks)) for rid, toks in out.items()}
        assert engine.trace_counts["decode_chunk"] == 1
        chunks = [r["attrs"] for r in recorder.records() if r["name"] == "serve.decode_chunk"]
        assert chunks and all(c["read_impl"] == impl for c in chunks)
        if speculative:
            assert engine.stats["speculative"]["verify_steps"] > 0
    assert tokens["pallas_paged"] == tokens["xla"]
    # three slots of 1..3 entries (runs of 4 pages of a window of 12): the kernel's own count
    assert all(3 <= c["read_blocks"] <= 9 for c in chunks) and max(c["read_blocks"] for c in chunks) > 3
    assert attention.read_blocks(np.asarray([0, 31, 32, 95]), *engine._read_shape) == 1 + 1 + 2 + 3


def test_the_read_itself_refuses_a_latent_pool_it_cannot_serve():
    import flax.linen as nn

    from accelerate_tpu.ops.attention import slot_cache_attention

    class Layer(nn.Module):
        impl: str = "xla"
        pool: str = "bf16"

        @nn.compact
        def __call__(self, q, row, positions, table):
            return slot_cache_attention(self, q, row, None, 32, positions, page_table=table, page_size=PAGE,
                                        num_pages=9, attention_impl=self.impl, kv_cache_dtype=self.pool,
                                        scale=0.2, value_dim=32)

    q, row = jnp.zeros((2, 1, 4, 128)), jnp.zeros((2, 1, 128))
    positions, table = jnp.zeros((2, 1), jnp.int32), jnp.zeros((2, 4), jnp.int32)
    # a row of 96 values is not whole lanes: the kernel would have to stage the pool, and never does
    with pytest.raises(ValueError, match="rows of 96 values.*latent rows are never staged"):
        Layer(impl="pallas_paged").init(jax.random.key(0), q[..., :96], row[..., :96], positions, table)
    with pytest.raises(ValueError, match="quantized pool for latent rows"):
        Layer(pool="int8").init(jax.random.key(0), q, row, positions, table)
    for impl in ("xla", "pallas_paged"):  # a pool of whole tiles is served by both reads
        out = Layer(impl=impl).init_with_output(jax.random.key(0), q, row, positions, table)[0]
        assert out.shape == (2, 1, 4, 32)


def test_registry_names_the_family():
    from accelerate_tpu.models import create_named_model, get_model_config

    assert get_model_config("kimi-vl-a3b-text")["n_routed_experts"] == 64
    tiny = create_named_model("latent-moe-tiny")
    assert isinstance(tiny.module, LatentMoEForCausalLM)
    assert dataclasses.replace(tiny.module.config, num_hidden_layers=27).num_moe_layers == 26
