"""The cached prefill's attention that stops at the causal frontier
(`ops/frontier_attention.py`), interpreted on the CPU:

  (a) the kernel against the masked XLA attention (`update_decode_cache`'s
      mask over the whole window) on the real rows — at the cache's start,
      behind a prefix that is no multiple of a block, flush with the window's
      end, at fewer rows than a key block, at a row count that is no whole
      tile, at a window its key block does not divide, with keys of 192 and
      values of 128, with 128 and 128, and with the 64 of 192 that all heads
      share passed once;
  (b) the latent families' modules (`latent_moe`, `latent_moe_hc`): a suffix
      prefilled into a dense cache behind a matched prefix gives, through the
      kernel, the logits and the cache the masked XLA branch gives;
  (c) what chooses it is what the call can observe, and an engine's
      `serve.insert` span counts the key blocks only where the kernel runs.

Off a TPU the modules run the XLA branch: the tests steer `frontier_serves`
(the one place the backend is asked), never a program option.
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

from accelerate_tpu.generation import make_cached_prefill_program  # noqa: E402
from accelerate_tpu.models import latent_moe  # noqa: E402
from accelerate_tpu.ops import frontier_attention as frontier  # noqa: E402
from accelerate_tpu.ops.attention import dot_product_attention  # noqa: E402
from accelerate_tpu.serving import ContinuousBatcher, Request  # noqa: E402


@pytest.fixture(autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def _masked_xla(q, k, v, cur, scale, shared_k=None):
    """`update_decode_cache`'s mask over the whole window, through the XLA
    attention the modules call off a TPU. q [B, H, rows, D], k/v [B, H, L, D]."""
    b, heads, rows, _ = q.shape
    window = k.shape[2]
    if shared_k is not None:
        k = jnp.concatenate([k, jnp.broadcast_to(shared_k[:, None], (b, heads, window, shared_k.shape[-1]))], axis=-1)
    at = cur + jnp.arange(rows)[:, None]
    cols = jnp.arange(window)[None, :]
    mask = jnp.broadcast_to(((cols <= at) & (cols < cur + rows))[None, None], (b, 1, rows, window))
    bshd = lambda x: x.transpose(0, 2, 1, 3).astype(jnp.float32)  # noqa: E731
    out = dot_product_attention(bshd(q), bshd(k), bshd(v), mask=mask, scale=scale, causal=False, implementation="xla")
    return out.reshape(b, rows, -1)


#: (rows, window, cur, heads, key, shared, value, block_q, block_k); a block of query rows is whole 128-lane tiles
CASES = {
    "cache_start": (256, 640, 0, 2, 128, 64, 128, 128, 128),
    "prefix_off_a_block": (256, 640, 37, 2, 128, 64, 128, 128, 128),
    "flush_with_the_window": (256, 640, 384, 2, 128, 64, 128, 128, 128),
    "rows_short_of_a_key_block": (32, 256, 150, 2, 128, 64, 128, 128, 128),
    "rows_no_whole_tile": (200, 512, 300, 2, 128, 64, 128, 128, 128),
    "window_the_block_does_not_divide": (256, 640, 300, 2, 128, 64, 128, 128, 256),
    "one_step_one_block": (48, 128, 5, 3, 128, 64, 128, 512, 512),
    "keys_192_values_128": (256, 640, 37, 2, 192, 0, 128, 128, 128),
    "keys_128_values_128": (128, 256, 101, 2, 128, 0, 128, 128, 128),
    "values_256_two_entries": (128, 256, 64, 1, 128, 0, 256, 128, 128),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_interpreted_equals_the_masked_xla_attention(case, dtype, monkeypatch):
    rows, window, cur, heads, key_dim, shared_dim, value_dim, block_q, block_k = CASES[case]
    monkeypatch.setattr(frontier, "BLOCK_Q", block_q)
    monkeypatch.setattr(frontier, "BLOCK_K", block_k)
    batch = 2 if case == "values_256_two_entries" else 1
    keys = jax.random.split(jax.random.key(3), 4)
    normal = lambda key, shape: jax.random.normal(key, shape, jnp.float32).astype(dtype)  # noqa: E731
    q = normal(keys[0], (batch, heads, rows, key_dim + shared_dim))
    k = normal(keys[1], (batch, heads, window, key_dim))
    v = normal(keys[2], (batch, heads, window, value_dim))
    shared_k = normal(keys[3], (batch, window, shared_dim)) if shared_dim else None
    scale = (key_dim + shared_dim) ** -0.5
    got = frontier.frontier_attention(  # the kernel's layouts: queries and values transposed
        q.transpose(0, 1, 3, 2), k, v.transpose(0, 1, 3, 2), jnp.int32(cur), scale=scale, shared_k=shared_k)
    assert got.shape == (batch, rows, heads * value_dim) and got.dtype == dtype
    want = _masked_xla(q, k, v, cur, scale, shared_k)
    # bfloat16: the probabilities enter the second product rounded to 8 bits and the result is stored in 8
    atol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=atol, rtol=0)


@pytest.mark.parametrize("cur,rows,window,want", [
    (0, 1024, 2176, (3, 10)), (0, 256, 2176, (1, 5)), (0, 2048, 2176, (10, 20)), (100, 2048, 2176, (14, 20)),
    (200, 1024, 2176, (5, 10)), (1024, 1024, 2048, (7, 8)),
], ids=lambda v: str(v))
def test_the_spans_count_is_the_kernels_walk(cur, rows, window, want):
    """`frontier_key_blocks` at the shipped block sizes (512 query rows, 512
    key rows): a 1,024-row insert of an empty slot visits 1 + 2 of 2 x 5 blocks."""
    assert (frontier.BLOCK_Q, frontier.BLOCK_K) == (512, 512)
    assert frontier.frontier_key_blocks(cur, rows, window) == want


def test_the_kernel_is_chosen_by_what_the_call_can_observe(monkeypatch):
    args = dict(window=2176, key_dim=128, value_dim=128, shared_dim=64, itemsize=2)
    assert frontier.frontier_refuses(**args) is None
    assert "whole" in frontier.frontier_refuses(**{**args, "window": 2100})
    assert "whole" in frontier.frontier_refuses(**{**args, "value_dim": 16})
    assert "VMEM" in frontier.frontier_refuses(**{**args, "window": 32768})
    assert not frontier.frontier_serves(1024, **args)  # the CPU: the masked XLA product
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert frontier.frontier_serves(1024, **args)
    assert not frontier.frontier_serves(1, **args)  # a decode step through the dense cache
    assert not frontier.frontier_serves(1024, **{**args, "window": 32768})
    with pytest.raises(ValueError, match="frontier_attention: a window of 100"):
        frontier.frontier_attention(jnp.zeros((1, 1, 128, 16)), jnp.zeros((1, 1, 100, 128)), jnp.zeros((1, 1, 128, 100)),
                                    jnp.int32(0), scale=1.0)
    with pytest.raises(ValueError, match="do not meet keys"):
        frontier.frontier_attention(jnp.zeros((1, 1, 192, 16)), jnp.zeros((1, 1, 128, 128)), jnp.zeros((1, 1, 128, 128)),
                                    jnp.int32(0), scale=1.0)


# ----------------------------------------------------------------- the modules
def _wide(config):
    """A tiny preset at the published head sizes (128 + 64 and 128), which the kernel takes."""
    return dataclasses.replace(config, num_attention_heads=2, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)


@pytest.fixture
def kernel_serves(monkeypatch):
    """The kernel wherever a TPU would run it; interpreted, since the backend is still the CPU."""
    monkeypatch.setattr(
        frontier, "frontier_serves", lambda rows, *shape: rows > 1 and frontier.frontier_refuses(*shape) is None)


FAMILIES = {"latent_moe": latent_moe.latent_moe_tiny, "latent_moe_hc": latent_moe.latent_moe_hc_tiny}


def _suffix_behind_a_prefix(model, prefix=24, suffix=48, window=256):
    """Prefill `prefix` tokens into a fresh dense cache of `window` positions,
    then `suffix` more behind them, both through the engine's own insert
    program; the second call's logits (at row 40) and the cache it leaves."""
    module = type(model.module)(dataclasses.replace(model.module.config, decode_cache_length=window))
    program = jax.jit(make_cached_prefill_program(module, lambda params: params))
    ids = np.random.default_rng(5).integers(1, 500, (1, prefix + suffix)).astype(np.int32)
    empty = module.init(jax.random.key(0), jnp.zeros((1, 1), jnp.int32), None, jnp.zeros((1, 1), jnp.int32))["cache"]
    cache = jax.tree_util.tree_map(jnp.zeros_like, empty)
    _, cache = program(model.params, cache, jnp.asarray(ids[:, :prefix]), jnp.arange(prefix)[None], None,
                       jnp.asarray([prefix - 1]))
    return program(model.params, cache, jnp.asarray(ids[:, prefix:]), prefix + jnp.arange(suffix)[None], None,
                   jnp.asarray([40]))


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_modules_cached_prefill_through_the_kernel_is_the_xla_branchs(family, monkeypatch, request):
    model = latent_moe.create_latent_moe_model(_wide(FAMILIES[family]()), jax.random.key(7))
    want_logits, want_cache = _suffix_behind_a_prefix(model)
    calls = []
    real = frontier.frontier_attention
    monkeypatch.setattr(frontier, "frontier_attention", lambda *a, **k: calls.append(a[0].shape) or real(*a, **k))
    request.getfixturevalue("kernel_serves")
    logits, cache = _suffix_behind_a_prefix(model)
    layers = model.module.config.num_hidden_layers
    assert calls == [(1, 2, 192, 24)] * layers + [(1, 2, 192, 48)] * layers  # every layer of both prefills
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits), atol=2e-5, rtol=0)
    for got, want in zip(jax.tree_util.tree_leaves(cache), jax.tree_util.tree_leaves(want_cache)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=0)


def test_a_key_padding_mask_keeps_the_masked_xla_attention(kernel_serves, monkeypatch):
    """A mask in the call, or one an earlier call left in the cache, is a thing
    the module sees in its input: the kernel knows the causal mask alone."""
    model = latent_moe.create_latent_moe_model(_wide(latent_moe.latent_moe_tiny()), jax.random.key(7))
    module = type(model.module)(dataclasses.replace(model.module.config, decode_cache_length=128))
    monkeypatch.setattr(frontier, "frontier_attention", lambda *a, **k: pytest.fail("the kernel met a padding mask"))
    ids = jnp.asarray(np.random.default_rng(5).integers(1, 500, (2, 16)).astype(np.int32))
    pad = jnp.asarray(np.r_[[[0] * 4 + [1] * 12], [[1] * 16]].astype(np.int32))
    _, mutated = module.apply(model.params, ids, pad, None, mutable=["cache"])
    # a later block of rows, no mask in the call: the cache still holds the first call's
    module.apply({**model.params, "cache": mutated["cache"]}, ids[:, :4], None, 16 + jnp.arange(4)[None].repeat(2, 0),
                 mutable=["cache"])


def _serve(model, prompts):
    from accelerate_tpu.telemetry import FlightRecorder, Tracer

    tracer = Tracer(recorder=FlightRecorder())
    engine = ContinuousBatcher(model, num_slots=2, max_length=128, chunk_size=4, page_size=8, tracer=tracer)
    out = {}
    for i, prompt in enumerate(prompts):  # one at a time: the second prompt finds the first's pages in the prefix cache
        out.update(engine.run([Request(i, prompt, max_new_tokens=5)]))
    records = tracer.recorder.records()
    return ({i: list(np.asarray(tokens)) for i, tokens in out.items()},
            [r["attrs"] for r in records if r.get("kind") == "span" and r["name"] == "serve.insert"])


def test_an_engines_insert_span_counts_the_key_blocks_where_the_kernel_runs(monkeypatch, request):
    model = latent_moe.create_latent_moe_model(_wide(latent_moe.latent_moe_tiny()), jax.random.key(7))
    rng = np.random.default_rng(9)
    shared = rng.integers(1, 500, 24).astype(np.int32)
    prompts = [np.r_[shared, rng.integers(1, 500, 9)].astype(np.int32),
               np.r_[shared, rng.integers(1, 500, 30)].astype(np.int32)]
    want, plain = _serve(model, prompts)
    assert all("attn_key_blocks" not in attrs for attrs in plain)  # the CPU's inserts score the window
    request.getfixturevalue("kernel_serves")
    monkeypatch.setattr(frontier, "BLOCK_K", 128)
    got, inserts = _serve(model, prompts)
    assert got == want
    first, second = inserts
    # 33 tokens, nothing matched: one step of 64 rows over positions 0..63, 1 key block of 128 of 1
    assert (first["bucket"], first["prefix_hit_pages"]) == (64, 0)
    assert (first["attn_key_blocks"], first["attn_key_blocks_window"]) == (1, 1)
    # 54 tokens behind 24 matched: 32 rows at positions 24..55 — or, where the planner drops matched pages, its own numbers
    matched = second["prefix_hit_pages"] * 8
    assert matched > 0
    assert (second["attn_key_blocks"], second["attn_key_blocks_window"]) == frontier.frontier_key_blocks(
        matched, second["bucket"], 128)
