"""KV page pool + shared-prefix reuse tests (paging.PagePool,
`ops/attention.slot_cache_attention`, `utils/operations.tree_gather_pages`/
`tree_scatter_pages`, and the `ContinuousBatcher` engine).

The load-bearing contracts:
  1. the paged scatter/gather ops round-trip against a dense reference,
     including page-boundary writes and arbitrary pool permutations;
  2. greedy decode is TOKEN-IDENTICAL to the static Generator across slot
     reuse and shared-prefix scenarios, and the read matches the two
     references kept here: the gather-everything read and the dense
     one-row-per-slot read the program had until PR 29;
  3. slot/page reuse never exposes a prior occupant's tokens;
  4. admission is PAGE-based: request mixes whose worst-case rows exceed the
     old slot capacity are admitted and complete when their actual token
     footprint fits the pool;
  5. the PagePool ledger (refcounts, prefix registrations, LRU eviction) stays
     consistent through every admit/release/reset path.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.generation import generate
from accelerate_tpu.models.llama import LlamaConfig, create_llama_model
from accelerate_tpu.paging import SCRATCH_PAGE, PagePool, chain_hashes
from accelerate_tpu.serving import ContinuousBatcher, Request

pytestmark = pytest.mark.paging


def _model(max_pos=64):
    cfg = LlamaConfig(
        vocab_size=128,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=max_pos,
        rope_theta=10000.0,
    )
    return create_llama_model(cfg, seq_len=32)


def _static_reference(model, prompt, max_new, **kwargs):
    out = np.asarray(generate(model, prompt[None, :], max_new_tokens=max_new, **kwargs))
    return out[0, prompt.size:]


# ------------------------------------------------------------------ tree ops


def _fake_caches(rng, layers=2, pages=7, ps=4, h=2, d=3):
    """(pool_tree, dense_struct) with the real leaf names at realistic ranks."""
    pool = {
        f"layer_{i}": {
            "attention": {
                "cached_key": jnp.asarray(rng.normal(size=(pages, ps, h, d)), jnp.float32),
                "cached_value": jnp.asarray(rng.normal(size=(pages, ps, h, d)), jnp.float32),
            }
        }
        for i in range(layers)
    }
    dense_struct = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((1, 3 * ps, *x.shape[2:]), x.dtype),
        pool,
    )
    for i in range(layers):
        dense_struct[f"layer_{i}"]["attention"]["cache_index"] = jax.ShapeDtypeStruct(
            (), jnp.int32
        )
    return pool, dense_struct


def test_gather_pages_matches_dense_reference():
    """Gathering pages [ids] must equal concatenating those pool pages in table
    order — the dense row a slot's window is."""
    from accelerate_tpu.utils.operations import tree_gather_pages

    rng = np.random.default_rng(0)
    pool, struct = _fake_caches(rng)
    ids = jnp.asarray([5, 2, 6], jnp.int32)
    dense = tree_gather_pages(pool, struct, ids, jnp.int32(8))
    for i in range(2):
        leaf = pool[f"layer_{i}"]["attention"]["cached_key"]
        expect = np.concatenate([np.asarray(leaf[p]) for p in (5, 2, 6)], axis=0)[None]
        np.testing.assert_array_equal(
            np.asarray(dense[f"layer_{i}"]["attention"]["cached_key"]), expect
        )
        assert int(dense[f"layer_{i}"]["attention"]["cache_index"]) == 8


def test_scatter_pages_roundtrip_and_untouched_pages():
    """scatter(gather(pool)) is the identity on the table's pages and leaves
    every OTHER page bit-for-bit untouched (page-boundary writes stay inside
    their page)."""
    from accelerate_tpu.utils.operations import tree_gather_pages, tree_scatter_pages

    rng = np.random.default_rng(1)
    pool, struct = _fake_caches(rng)
    ids = jnp.asarray([1, 4, 3], jnp.int32)
    dense = tree_gather_pages(pool, struct, ids, jnp.int32(0))
    out = tree_scatter_pages(pool, dense, ids)
    for a, b in zip(jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(pool)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # A modified dense row lands in exactly the right page at the right offset.
    key = dense["layer_0"]["attention"]["cached_key"]
    key = key.at[0, 5].set(99.0)  # logical position 5 = page ids[1]=4, offset 1
    dense["layer_0"]["attention"]["cached_key"] = key
    out = tree_scatter_pages(pool, dense, ids)
    got = np.asarray(out["layer_0"]["attention"]["cached_key"])
    np.testing.assert_array_equal(got[4, 1], np.full((2, 3), 99.0))
    # neighbours of the write untouched
    src = np.asarray(pool["layer_0"]["attention"]["cached_key"])
    np.testing.assert_array_equal(got[4, 0], src[4, 0])
    np.testing.assert_array_equal(got[0], src[0])


def _gathered_window(pool, table, scale_pool=None, dtype=None):
    """Every slot's whole logical window, gathered through its page table
    ([B, P * page_size, h, d]), live or not: the view the gather-everything
    read attended."""
    from accelerate_tpu.ops.quantization import dequantize_kv_pages

    pages = jnp.take(pool, table, axis=0, mode="clip")  # [B, P, ps, h, d]
    if scale_pool is not None:
        pages = dequantize_kv_pages(pages, jnp.take(scale_pool, table, axis=0, mode="clip"), dtype)
    b, pages_per_slot, ps, h, d = pages.shape
    return pages.reshape(b, pages_per_slot * ps, h, d)


def _gather_everything_attention(q, pool_k, pool_v, pos, table, scales):
    """ORACLE, a drop-in for `ops.attention._live_page_attention`: the paged
    XLA read as the program had it until PR 28 — gather every slot's whole
    window, mask `cols <= pos`, `dot_product_attention`. 3 x slots x window
    bytes a layer whatever is live, which is why it lives here now."""
    from accelerate_tpu.ops.attention import dot_product_attention

    k_scale, v_scale = scales if scales is not None else (None, None)
    k_full = _gathered_window(pool_k, table, k_scale, q.dtype)
    v_full = _gathered_window(pool_v, table, v_scale, q.dtype)
    cols = jnp.arange(k_full.shape[1])[None, None, :]
    mask = (cols <= pos[:, :, None])[:, None, :, :]  # [B, 1, s, L]
    return dot_product_attention(q, k_full, v_full, mask=mask, causal=False)


def _dense_slot_attention(module, q, k, v, cache_length, positions):
    """REFERENCE, the dense one-row-per-slot read the program had until PR 29
    (`ops.attention.update_slot_cache` + `dot_product_attention`): the cache
    is [B, cache_length, h, d], row i's new K/V lands at `positions[i]`, and
    each query attends `cols <= its position` of its own row — every slot's
    whole row is read, whatever is live."""
    from accelerate_tpu.ops.attention import dot_product_attention

    b, s, h, d = k.shape
    L = cache_length
    cached_k = module.variable("cache", "cached_key", jnp.zeros, (b, L, h, d), k.dtype)
    cached_v = module.variable("cache", "cached_value", jnp.zeros, (b, L, h, d), v.dtype)
    pos = jnp.clip(positions, 0, L - 1).astype(jnp.int32)  # [B, s]
    rows = jnp.arange(b)[:, None]
    cached_k.value = cached_k.value.at[rows, pos].set(k)
    cached_v.value = cached_v.value.at[rows, pos].set(v)
    cols = jnp.arange(L)[None, None, :]
    mask = (cols <= pos[:, :, None])[:, None, :, :]  # [B, 1, s, L]
    return dot_product_attention(q, cached_k.value, cached_v.value, mask=mask, causal=False)


def test_paged_slot_write_crosses_page_boundaries():
    """The paged write lands at pool[table[pos//ps], pos%ps] and the window
    gathered through the table reproduces the dense logical order, for
    positions on both sides of every page boundary."""
    import flax.linen as nn

    from accelerate_tpu.ops.attention import _write_slot_pool

    ps, num_pages, P = 4, 6, 3

    class Probe(nn.Module):
        @nn.compact
        def __call__(self, k, v, positions, page_table):
            pool_k, _pool_v, pos, table, _scales = _write_slot_pool(
                self, k, v, positions, page_table, ps, num_pages
            )
            return _gathered_window(pool_k, table), pos

    probe = Probe()
    table = jnp.asarray([[2, 5, 1], [4, 3, 0]], jnp.int32)  # two slots
    cache = None
    rng = np.random.default_rng(2)
    written = {}
    for pos in (0, 3, 4, 7, 8, 11):  # page starts and page ends
        k = jnp.asarray(rng.normal(size=(2, 1, 2, 3)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, 1, 2, 3)), jnp.float32)
        positions = jnp.full((2, 1), pos, jnp.int32)
        variables = {"cache": cache} if cache is not None else {}
        (k_full, clipped), mutated = probe.apply(
            variables, k, v, positions, table, mutable=["cache"]
        )
        cache = mutated["cache"]
        written[pos] = np.asarray(k)
        # the gathered logical view holds every row written so far, in order
        for p_seen, kk in written.items():
            np.testing.assert_array_equal(np.asarray(k_full)[:, p_seen], kk[:, 0])
        np.testing.assert_array_equal(np.asarray(clipped), np.full((2, 1), pos))
    # physical placement: slot 0 wrote pages 2,5,1; slot 1 wrote 4,3,0
    pool_k = np.asarray(cache["cached_key"])
    np.testing.assert_array_equal(pool_k[5, 3], written[7][0, 0])  # slot 0, pos 7
    np.testing.assert_array_equal(pool_k[3, 0], written[4][1, 0])  # slot 1, pos 4


# --------------------------------------------------------- the live-page read


def _read_layer(read, page_size, num_pages, kv_cache_dtype="bf16"):
    """One attention layer over a slot cache: `read` "live" is the program's
    read, "everything" the oracle above behind the same write, and
    "contiguous" the dense reference above over one row a slot."""
    import flax.linen as nn

    from accelerate_tpu.ops import attention

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, q, k, v, positions, table):
            length = table.shape[-1] * page_size
            if read == "contiguous":
                return _dense_slot_attention(self, q, k, v, length, positions)
            if read == "live":
                return attention.slot_cache_attention(
                    self, q, k, v, length, positions, page_table=table, page_size=page_size,
                    num_pages=num_pages, kv_cache_dtype=kv_cache_dtype,
                )
            pools = attention._write_slot_pool(
                self, k, v, positions, table, page_size, num_pages, kv_cache_dtype=kv_cache_dtype
            )
            pool_k, pool_v, pos, clipped_table, scales = pools
            return _gather_everything_attention(q, pool_k, pool_v, pos, clipped_table, scales)

    return Layer()


_POOL_DTYPES = {"int8": jnp.int8, "fp8_e4m3": jnp.float8_e4m3fn}


def _read_case(first_positions, s=1, hq=4, hkv=2, pool="bf16", dtype=jnp.float32, seed=0, peak=None,
               peak_score=30.0):
    """Slots at `first_positions` (each row's queries sit at first..first+s-1)
    over a shuffled pool with P=6 pages of 4 tokens a slot: returns the layer
    operands, a pool cache holding random history, and the same history laid
    out contiguously. A slot's table row holds pool pages for its live pages
    and the scratch page past them, as the engine leaves it.

    `peak=(slot, page)`: keys eight times as large, so scores range over +-30,
    and that page of that slot holds, for every query head of the slot's first
    query, a key along it that scores `peak_score` — the row's maximum lies
    where the case puts it (`_peak_page` checks)."""
    ps, P, d = 4, 6, 8
    rng = np.random.default_rng(seed)
    first = np.asarray(first_positions)
    b = first.size
    num_pages = b * P + 1
    table = np.zeros((b, P), np.int32)
    free = rng.permutation(np.arange(1, num_pages))
    for row in range(b):
        live = (first[row] + s - 1) // ps + 1
        table[row, :live] = free[row * P : row * P + live]
    spread = 1.0 if pool == "bf16" else 20.0  # quantized pages use their range
    pools = {
        name: rng.normal(size=(num_pages, ps, hkv, d)) * spread
        for name in ("cached_key", "cached_value")
    }
    scale_pools = {}
    if pool != "bf16":
        for name in ("key_scale", "value_scale"):
            scale_pools[name] = jnp.asarray(rng.uniform(0.01, 0.05, size=(num_pages, hkv)), jnp.float32)
    q, k, v = (rng.normal(size=(b, s, heads, d)) for heads in (hq, hkv, hkv))
    if peak is not None:
        row, page = peak
        rep = hq // hkv
        pools["cached_key"] *= 8.0
        k *= 8.0
        for head in range(hq):  # token `head % rep` of the page, the head's kv head
            along = q[row, 0, head]
            pools["cached_key"][table[row, page], head % rep, head // rep] = (
                along * peak_score * np.sqrt(d) / (along @ along)
            )
    pools = {name: jnp.asarray(x, jnp.float32) for name, x in pools.items()}
    contiguous = {name: _gathered_window(x, jnp.asarray(table)).astype(dtype) for name, x in pools.items()}
    cache = {name: x.astype(_POOL_DTYPES.get(pool, dtype)) for name, x in pools.items()}
    cache.update(scale_pools)
    q, k, v = (jnp.asarray(x, dtype) for x in (q, k, v))
    positions = jnp.asarray(first[:, None] + np.arange(s)[None, :], jnp.int32)
    operands = (q, k, v, positions, jnp.asarray(table))
    return operands, cache, contiguous, (ps, num_pages)


def _peak_page(operands, contiguous, row):
    """The page of slot `row`'s window in which each query head of its first
    query scores highest over the history below its position ([Hq] pages),
    by plain numpy on the contiguous layout."""
    q, _k, _v, positions, _table = operands
    keys = np.asarray(contiguous["cached_key"][row], np.float32)  # [L, Hkv, d]
    query = np.asarray(q[row, 0], np.float32)  # [Hq, d]
    rep = query.shape[0] // keys.shape[1]
    scores = np.einsum("hd,lhd->hl", query, np.repeat(keys, rep, axis=1))
    scores[:, int(positions[row, 0]):] = -np.inf  # the position itself is this dispatch's k
    return scores.argmax(axis=1) // 4


def _run_read(read, operands, cache, geometry, pool="bf16"):
    layer = _read_layer(read, *geometry, kv_cache_dtype=pool)
    out, _ = jax.jit(lambda c, *a: layer.apply({"cache": c}, *a, mutable=["cache"]))(cache, *operands)
    return np.asarray(out, np.float32)


@pytest.fixture
def block_pages(monkeypatch):
    """Set the live-page read's block to `pages` pages of the case's K, through
    the one constant it derives its block from (tiny test windows otherwise fit
    one block, and the loop runs once)."""
    from accelerate_tpu.ops import attention

    def set_block(pages, operands):
        _q, k, *_ = operands
        page_bytes = 4 * k.shape[2] * k.shape[3] * k.dtype.itemsize  # page_size 4
        monkeypatch.setattr(attention, "_READ_BLOCK_BYTES", pages * page_bytes)

    return set_block


# Positions over P=6 pages of 4: slot 0 at position 0, slot 1 at max_length - 1,
# slot 2 on a page boundary, slot 3 one short of one, slot 4 on another.
_RAGGED = (0, 23, 4, 3, 8)  # live pages 1 + 6 + 2 + 1 + 3 = 13


@pytest.mark.parametrize(
    "first,block,kwargs",
    [
        pytest.param(_RAGGED, 4, {}, id="ragged-gqa-partial-last-block"),
        pytest.param(_RAGGED, 4, {"hq": 4, "hkv": 4}, id="ragged-mha"),
        pytest.param(_RAGGED, 1, {}, id="one-page-blocks"),
        pytest.param((0, 23, 4, 3, 4), 4, {}, id="n-is-3-blocks-exactly"),  # 1+6+2+1+2 = 12
        pytest.param((0,), 4, {}, id="n-is-1"),
        pytest.param(_RAGGED, None, {}, id="window-fits-one-block"),
        pytest.param(_RAGGED, 4, {"dtype": jnp.bfloat16}, id="bf16"),
        pytest.param((0, 19, 4, 3, 8), 4, {"s": 5}, id="verify5"),  # 19 + 4 = max_length - 1
        pytest.param((0, 19, 4, 3, 8), 4, {"s": 5, "hq": 4, "hkv": 4}, id="verify5-mha"),
        pytest.param(_RAGGED, 4, {"pool": "int8"}, id="int8"),
        pytest.param(_RAGGED, 4, {"pool": "fp8_e4m3"}, id="fp8"),
        pytest.param((0, 19, 4, 3, 8), 4, {"s": 5, "pool": "int8"}, id="int8-verify5"),
        # What only a running softmax can get wrong. One slot of 6 live pages in
        # blocks of 2: its row's maximum (scores over +-30) is met in the first
        # block, so every later block rescales nothing and must add little; in
        # the middle; or in the last, so all that was summed is rescaled.
        *(
            pytest.param((23,), 2, {"peak": (0, page), **extra}, id=f"max-in-{where}-block{tag}")
            for extra, tag in (({}, ""), ({"dtype": jnp.bfloat16}, "-bf16"))
            for page, where in ((0, "first"), (3, "middle"), (5, "last"))
        ),
        # The same beside other owners in its blocks: [idle, long | long, long |
        # long, long | long, one-page] — a block's maximum by owner, not by block.
        *(
            pytest.param((0, 23, 3), 2, {"peak": (1, page)}, id=f"max-in-{where}-block-shared")
            for page, where in ((0, "first"), (2, "middle"), (5, "last"))
        ),
        pytest.param((0, 23, 3), 2, {"peak": (1, 5), "dtype": jnp.bfloat16},
                     id="max-in-last-block-shared-bf16"),
        # A neighbour's maximum must not be this row's: 120 above the one-page slot's
        # scores, it would underflow every one of them and leave 0 / 0.
        pytest.param((0, 23, 3), 2, {"peak": (1, 5), "peak_score": 120.0},
                     id="max-by-owner-not-by-block"),
        pytest.param((0, 19, 4, 3, 8), 1, {"s": 5}, id="one-page-blocks-verify5"),
        # 1 + 4 = 5 entries in blocks of 4: the last block is one entry and a tail of nobody's.
        pytest.param((0, 15), 4, {"peak": (1, 3)}, id="last-block-one-entry-then-unlisted"),
        pytest.param((0, 14), 4, {"s": 2}, id="last-block-one-entry-then-unlisted-verify2"),
    ],
)
def test_live_page_read_matches_gather_everything_and_contiguous(first, block, kwargs, block_pages):
    """The paged XLA read (one pass over blocks of live pages under a trip
    count from the positions, a running softmax by owner) == the
    gather-everything oracle == the dense reference, on the layer's output:
    ragged positions, block boundaries, verify blocks, GQA and MHA, quantized
    pools (which the dense reference does not have), and a row's maximum met
    early, midway or late in its blocks."""
    operands, cache, contiguous, geometry = _read_case(first, **kwargs)
    pool = kwargs.get("pool", "bf16")
    if "peak" in kwargs:
        row, page = kwargs["peak"]
        np.testing.assert_array_equal(_peak_page(operands, contiguous, row), page)
    if block is not None:
        block_pages(block, operands)
    live = _run_read("live", operands, cache, geometry, pool)
    assert np.isfinite(live).all()
    tol = 2e-2 if kwargs.get("dtype") is jnp.bfloat16 else 2e-5
    everything = _run_read("everything", operands, cache, geometry, pool)
    np.testing.assert_allclose(live, everything, atol=tol, rtol=tol)
    if pool == "bf16":
        dense = _run_read("contiguous", operands, contiguous, geometry)
        np.testing.assert_allclose(live, dense, atol=tol, rtol=tol)


def test_idle_slot_at_position_zero_contributes_nothing(block_pages):
    """An idle slot as the engine leaves it — table row all scratch, position 0
    — is one entry of the live list (the scratch page): the other slots' rows
    read what they read without it, whatever the scratch page holds, and its
    own row is finite."""
    operands, cache, _, geometry = _read_case((9, 0, 14))
    q, k, v, positions, table = operands
    table = table.at[1].set(SCRATCH_PAGE)
    cache = {name: x.at[SCRATCH_PAGE].set(1e4) for name, x in cache.items()}  # loud garbage
    block_pages(2, operands)
    with_idle = _run_read("live", (q, k, v, positions, table), cache, geometry)
    busy = np.asarray([0, 2])
    without = _run_read(
        "live", tuple(x[busy] for x in (q, k, v, positions, table)), cache, geometry
    )
    assert np.isfinite(with_idle).all()
    np.testing.assert_allclose(with_idle[busy], without, atol=2e-6, rtol=2e-6)


def _decode_logits(engine):
    """One more decode step's logits off an engine's live state (its own
    un-jitted step program; nothing is donated or adopted)."""
    token, pos, _active, _rem = engine._carry  # the slot state lives on the device
    args = [engine.params, engine._cache, token, pos, jnp.asarray(engine._slots.page_table)]
    logits, _cache = jax.jit(engine._step_raw)(*args)
    return np.asarray(logits, np.float32)


@pytest.mark.parametrize("family", ["llama-gqa", "gpt_neox-mha"])
def test_paged_read_logits_match_oracle_and_contiguous(family, monkeypatch):
    """Model level, both slot-cache families: two engines two chunks into the
    same ragged requests (one slot left idle) — the program's read, and the
    gather-everything oracle in its place — hold the same state and score the
    next step alike, and what they streamed so far is the static Generator's
    (its dense decode cache is the other layout)."""
    import dataclasses

    from accelerate_tpu.ops import attention

    if family == "llama-gqa":
        model = _model()
    else:
        from accelerate_tpu.models.gpt_neox import create_gpt_neox_model, gpt_neox_tiny

        model = create_gpt_neox_model(
            dataclasses.replace(gpt_neox_tiny(), max_position_embeddings=64), seq_len=32
        )
    vocab = model.module.config.vocab_size
    rng = np.random.default_rng(8)
    prompts = [rng.integers(1, vocab, (n,)).astype(np.int32) for n in (3, 16, 9)]
    # One K page of these models is 8 tokens x kv heads x head_dim: blocks of 2 pages.
    cfg = model.module.config
    kv_heads = getattr(cfg, "num_key_value_heads", cfg.num_attention_heads)
    monkeypatch.setattr(attention, "_READ_BLOCK_BYTES", 2 * 8 * kv_heads * cfg.head_dim * 4)

    def two_chunks():
        engine = ContinuousBatcher(model, num_slots=4, max_length=32, chunk_size=2, page_size=8)
        for i, p in enumerate(prompts):
            engine.submit(Request(i, p, max_new_tokens=12))
        engine.step()
        engine.step()
        return engine

    live = two_chunks()
    assert live._slots.pos[3] == 0 and not live._slots.active[3]  # the idle slot
    busy = np.arange(3)
    live_logits = _decode_logits(live)[busy]
    for i, p in enumerate(prompts):
        streamed = live.results[i].tokens
        assert len(streamed) == 5  # the insert's token and two chunks of 2
        np.testing.assert_array_equal(streamed, _static_reference(model, p, 12)[:5])
    monkeypatch.setattr(attention, "_live_page_attention", _gather_everything_attention)
    everything = two_chunks()
    np.testing.assert_allclose(live_logits, _decode_logits(everything)[busy], atol=2e-5)
    for mine, theirs in zip(live._carry, everything._carry):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    np.testing.assert_array_equal(live._slots.pos, np.asarray(live._carry[1]))  # the host's prediction


# ------------------------------------------------------------------ parity


def _slot_reuse_workload(rng):
    lengths = [5, 9, 3, 12, 7, 4]
    budgets = [6, 4, 8, 3, 5, 7]
    return [rng.integers(1, 128, (n,)).astype(np.int32) for n in lengths], budgets


def _shared_prefix_churn_workload(rng):
    """Two system prompts of two full pages each (page_size 8), interleaved, more
    requests than slots: shared pages, private pages, frees and slot reuse all
    pass through the page tables the "xla" read gathers from."""
    systems = [rng.integers(1, 128, (17,)).astype(np.int32) for _ in range(2)]
    tails = [2, 5, 1, 4, 3, 6, 2]
    prompts = [
        np.concatenate([systems[i % 2], rng.integers(1, 128, (n,)).astype(np.int32)])
        for i, n in enumerate(tails)
    ]
    return prompts, [4, 7, 3, 6, 5, 2, 8]


@pytest.mark.parametrize(
    "workload", [_slot_reuse_workload, _shared_prefix_churn_workload],
    ids=["slot_reuse", "shared_prefix_churn"],
)
def test_paged_contiguous_and_static_parity_with_slot_reuse(workload):
    """Acceptance pin: greedy decode through the "xla" read is token-identical
    to the static Generator (a dense decode cache, one request at a time)
    across a slot-reuse workload and a shared-prefix one."""
    model = _model()
    prompts, budgets = workload(np.random.default_rng(3))
    requests = lambda: [  # noqa: E731 — fresh Request objects per engine
        Request(i, p, max_new_tokens=m) for i, (p, m) in enumerate(zip(prompts, budgets))
    ]
    paged = ContinuousBatcher(
        model, num_slots=2, max_length=32, chunk_size=4, page_size=8, attention_impl="xla"
    )
    out_p = paged.run(requests())
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        np.testing.assert_array_equal(out_p[i], _static_reference(model, p, m))
    assert paged.trace_counts["decode_chunk"] == 1
    assert paged.pool.pages_in_use == 0
    assert paged.pool.check_consistency() == []


def _assert_page_table_in_pool(engine):
    """What the "xla" read's unguarded gather (`mode="clip"`) relies on: every
    page-table entry is one of the pool's own ids, a live slot's row is its
    pages in order, and every unused entry is the scratch page."""
    table = engine._slots.page_table
    assert table.dtype == np.int32
    assert table.min() >= 0 and table.max() < engine.pool.num_pages, table
    for slot, pages in enumerate(engine._slot_pages):
        assert list(table[slot, : len(pages)]) == list(pages), (slot, table[slot], pages)
        assert SCRATCH_PAGE not in pages
        assert (table[slot, len(pages):] == SCRATCH_PAGE).all(), (slot, table[slot], pages)
        if engine._slot_request[slot] is None:
            assert pages == []


def test_page_table_entries_stay_in_the_pool_through_churn():
    """Admissions, prefix sharing, eviction under a tight pool, a cancel, frees
    and slot reuse: after every step the page table the decode chunk is about to
    gather from holds only in-range ids, with scratch in every unused entry."""
    model = _model()
    prompts, budgets = _shared_prefix_churn_workload(np.random.default_rng(8))
    rng = np.random.default_rng(9)
    prompts += [rng.integers(1, 128, (n,)).astype(np.int32) for n in (3, 11, 6)]
    budgets += [9, 2, 5]
    # 9 usable pages of 8 tokens for 3 slots of up to 4 pages: the pool, not the
    # slots, bounds admission, so cached prefix pages are evicted and re-minted.
    engine = ContinuousBatcher(
        model, num_slots=3, max_length=32, chunk_size=2, page_size=8, num_pages=10,
        attention_impl="xla",
    )
    _assert_page_table_in_pool(engine)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        engine.submit(Request(i, p, max_new_tokens=m))
    steps = 0
    while engine.pending:
        engine.step()
        steps += 1
        if steps == 2:
            # an in-flight request (a slot's row drops to scratch mid-run), or a
            # queued one (never admitted): whichever request 1 is by now
            engine.cancel(1)
        _assert_page_table_in_pool(engine)
        assert engine.pool.check_consistency() == []
        assert steps < 200
    assert (engine._slots.page_table == SCRATCH_PAGE).all()
    assert engine.pool.pages_in_use == 0
    assert engine.stats["prefix_cache"]["hits"] > 0 and engine.pool.evictions > 0
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        if i != 1:
            np.testing.assert_array_equal(
                np.asarray(engine.results[i].tokens), _static_reference(model, p, m)
            )


def test_shared_prefix_parity_and_tokens_saved():
    """Requests sharing a system prompt: greedy outputs stay token-identical to
    the static path AND to a prefix-cache-disabled engine, while the prefix
    cache demonstrably skips prefill work (prefill_tokens_saved > 0)."""
    model = _model()
    rng = np.random.default_rng(4)
    system = rng.integers(1, 128, (13,)).astype(np.int32)  # 3 full pages at ps=4
    prompts = [
        np.concatenate([system, rng.integers(1, 128, (n,)).astype(np.int32)])
        for n in (3, 6, 2, 5)
    ]
    requests = lambda: [Request(i, p, max_new_tokens=5) for i, p in enumerate(prompts)]  # noqa: E731
    cached = ContinuousBatcher(model, num_slots=2, max_length=64, chunk_size=4, page_size=4)
    plain = ContinuousBatcher(
        model, num_slots=2, max_length=64, chunk_size=4, page_size=4, prefix_cache=False
    )
    out_cached = cached.run(requests())
    out_plain = plain.run(requests())
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(out_cached[i], out_plain[i])
        np.testing.assert_array_equal(out_cached[i], _static_reference(model, p, 5))
    saved = cached.stats["prefix_cache"]["prefill_tokens_saved"]
    assert saved >= 3 * 4 * 3, saved  # 3 later requests x 3 shared pages x 4 tokens
    assert cached.stats["prefix_cache"]["hits"] >= 9
    assert plain.stats["prefix_cache"]["prefill_tokens_saved"] == 0
    # full-prompt page-aligned hit still produces first-token logits: a request
    # whose prompt is EXACTLY the cached pages must recompute its last token
    exact = np.asarray(system[:12])  # exactly 3 pages
    out = cached.run([Request(10, exact, max_new_tokens=4)])
    np.testing.assert_array_equal(out[10], _static_reference(model, exact, 4))


def test_gpt_neox_paged_parity():
    """The paged slot cache is model-layer plumbing for BOTH slot families."""
    import dataclasses

    from accelerate_tpu.models.gpt_neox import create_gpt_neox_model, gpt_neox_tiny

    cfg = dataclasses.replace(gpt_neox_tiny(), max_position_embeddings=64)
    model = create_gpt_neox_model(cfg, seq_len=32)
    rng = np.random.default_rng(5)
    shared = rng.integers(1, cfg.vocab_size, (9,)).astype(np.int32)
    prompts = [
        np.concatenate([shared, rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)])
        for n in (2, 4)
    ]
    engine = ContinuousBatcher(model, num_slots=2, max_length=32, chunk_size=4, page_size=8)
    outputs = engine.run([Request(i, p, max_new_tokens=5) for i, p in enumerate(prompts)])
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(outputs[i], _static_reference(model, p, 5))
    assert engine.stats["prefix_cache"]["prefill_tokens_saved"] == 8


def test_slot_reuse_never_exposes_prior_occupants_tokens():
    """A slot's (and its freed pages') next occupant with a SHORTER prompt and
    a longer budget must decode exactly as if the pool were fresh — the masked
    stale K/V from the previous occupant contributes exactly nothing."""
    model = _model()
    rng = np.random.default_rng(6)
    long_prompt = rng.integers(1, 128, (24,)).astype(np.int32)
    short_prompt = rng.integers(1, 128, (3,)).astype(np.int32)
    engine = ContinuousBatcher(model, num_slots=1, max_length=32, chunk_size=4, page_size=4)
    first = engine.run([Request(0, long_prompt, max_new_tokens=6)])
    np.testing.assert_array_equal(first[0], _static_reference(model, long_prompt, 6))
    # same single slot, same pages, different occupant
    second = engine.run([Request(1, short_prompt, max_new_tokens=12)])
    np.testing.assert_array_equal(second[1], _static_reference(model, short_prompt, 12))


def test_repeated_workload_mints_no_new_insert_buckets():
    """Steady-state no-recompile pin for prefix serving: re-serving prompts
    that registered their OWN pages matches deeper (tiny suffixes) — the
    page-size bucket floor must absorb those instead of minting ever-smaller
    insert executables. Pass 2 may deepen matches; pass 3 must compile
    NOTHING new and stay token-identical."""
    model = _model()
    rng = np.random.default_rng(8)
    system = rng.integers(1, 128, (10,)).astype(np.int32)
    prompts = [
        np.concatenate([system, rng.integers(1, 128, (n,)).astype(np.int32)])
        for n in (2, 5, 3)
    ]
    engine = ContinuousBatcher(model, num_slots=2, max_length=64, chunk_size=4, page_size=4)
    outputs = {}
    for round_no in range(3):
        if round_no == 2:
            stable = dict(engine.trace_counts)
        out = engine.run([Request(round_no * 10 + i, p, max_new_tokens=5) for i, p in enumerate(prompts)])
        outputs[round_no] = [out[round_no * 10 + i] for i in range(len(prompts))]
        for i in range(len(prompts)):
            engine.release(round_no * 10 + i)
    assert engine.trace_counts == stable, (stable, engine.trace_counts)
    assert engine.trace_counts["decode_chunk"] == 1
    for i in range(len(prompts)):
        np.testing.assert_array_equal(outputs[0][i], outputs[2][i])


# ------------------------------------------------------------------ admission


def test_page_based_admission_exceeds_old_slot_capacity():
    """Acceptance pin: a pool of 8x8=64 tokens backs FOUR concurrent slots
    whose worst-case rows (4 x max_length 64 = 256 tokens) would have required
    4x the HBM under the contiguous layout — and a fifth request queues on pool
    exhaustion, then completes once pages free (no deadlock, no error)."""
    model = _model()
    rng = np.random.default_rng(7)
    engine = ContinuousBatcher(
        model, num_slots=4, max_length=64, chunk_size=2, page_size=8, num_pages=9
    )
    prompts = [rng.integers(1, 128, (6,)).astype(np.int32) for _ in range(5)]
    for i in range(4):
        engine.submit(Request(i, prompts[i], max_new_tokens=10))  # 2 pages each
    engine.step()
    assert engine.free_slots == 0, "all four requests must be in flight at once"
    assert engine.pool.pages_in_use == 8
    engine.submit(Request(4, prompts[4], max_new_tokens=10))
    engine.step()
    assert not engine.results[4].tokens, "fifth request must wait for pages"
    outputs = engine.run()
    for i in range(5):
        assert engine.results[i].finish_reason == "length"
        np.testing.assert_array_equal(outputs[i], _static_reference(model, prompts[i], 10))
    assert engine.pool.pages_in_use == 0
    assert engine.pool.check_consistency() == []


def test_submit_rejects_requests_larger_than_the_pool():
    model = _model()
    engine = ContinuousBatcher(
        model, num_slots=2, max_length=64, chunk_size=2, page_size=8, num_pages=3
    )
    with pytest.raises(ValueError, match="KV pages"):
        engine.submit(Request(0, np.arange(1, 20, dtype=np.int32), max_new_tokens=8))
    # within the pool: fine
    engine.submit(Request(1, np.arange(1, 9, dtype=np.int32), max_new_tokens=8))
    engine.run()
    assert engine.results[1].finished


# ------------------------------------------------------------------ allocator


def test_chain_hashes_commit_to_the_whole_prefix():
    a = chain_hashes([1, 2, 3, 4, 5, 6, 7, 8, 9], 4)
    b = chain_hashes([1, 2, 3, 4, 5, 6, 7, 8], 4)
    c = chain_hashes([9, 2, 3, 4, 5, 6, 7, 8], 4)
    assert len(a) == 2 and len(b) == 2 and a == b  # partial trailing page unhashed
    assert c[0] != a[0] and c[1] != a[1]  # first-token change breaks EVERY page


def test_page_pool_refcounts_prefix_cache_and_eviction():
    pool = PagePool(num_pages=6, page_size=4)
    hashes = chain_hashes(list(range(8)), 4)
    pages = pool.reserve(3)
    assert pages is not None and SCRATCH_PAGE not in pages
    assert pool.pages_in_use == 3 and pool.pages_free == 2
    pool.register_prefix(hashes, pages)  # first two pages become shareable
    # a second request sharing both prefix pages pins them
    matched = pool.match_prefix(hashes, 2)
    assert matched == pages[:2]
    pool.release(matched)
    pool.release(pages)
    assert pool.pages_in_use == 0
    assert pool.pages_cached == 2 and pool.pages_free == 3  # prefix pages stay cached
    assert pool.check_consistency() == []
    # exhausting the free list evicts cached prefix pages LRU, oldest first
    big = pool.reserve(5)
    assert big is not None and pool.evictions == 2
    assert pool.prefix_entries == 0 and pool.match_prefix(hashes, 2) == []
    pool.release(big)
    assert pool.check_consistency() == []
    # over-reserve refuses without partially draining
    assert pool.reserve(6) is None
    assert pool.pages_free == 5


def test_eviction_trims_cached_prefix_chains_from_the_deep_end():
    """Pool pressure must degrade a cached prefix gracefully: evict the chain
    TAIL first so the surviving head pages still match — evicting the head
    would strand every deeper cached page of the chain unmatchable."""
    pool = PagePool(num_pages=5, page_size=4)
    hashes = chain_hashes(list(range(12)), 4)  # 3-page chain
    pages = pool.reserve(3)
    pool.register_prefix(hashes, pages)
    pool.release(pages)  # chain order in, all three now cached
    assert pool.pages_cached == 3 and pool.pages_free == 1
    taken = pool.reserve(2)  # 1 free + 1 eviction
    assert pool.evictions == 1
    # the DEEPEST page went; the head two still serve a partial match
    assert pool.match_prefix(hashes, 3) == pages[:2]
    pool.release(pages[:2])
    pool.release(taken)
    assert pool.check_consistency() == []


def test_page_pool_reset_forgets_prefixes_and_refuses_bad_release():
    pool = PagePool(num_pages=4, page_size=2)
    hashes = chain_hashes([1, 2, 3, 4], 2)
    pages = pool.reserve(2)
    pool.register_prefix(hashes, pages)
    pool.reset()
    assert pool.pages_in_use == 0 and pool.pages_free == 3
    assert pool.prefix_entries == 0, "reset must forget prefixes (content is gone)"
    assert pool.match_prefix(hashes, 2) == []
    with pytest.raises(ValueError, match="refcount"):
        pool.release([1])
    with pytest.raises(ValueError, match="scratch"):
        pool.release([SCRATCH_PAGE])
    assert pool.check_consistency() == []


def test_admission_bucket_planner_is_a_closed_set():
    """Satellite pin (the serving_bench first-hit recompile fix): over the
    WHOLE admission domain — every prompt length x prefix-match depth x
    several pool geometries — the planned insert bucket is a power of two or
    the single capped top value, the kept prefix still fits the cache window,
    and the suffix still fits the bucket. An open set of matched_len-dependent
    remainder buckets is exactly what used to compile a fresh insert on the
    first deep prefix hit of a timed run."""
    for page_size, padded in ((16, 128), (16, 120), (4, 40), (8, 72), (4, 24)):
        ladder_limit = padded
        for p in range(1, padded + 1):
            for matched in range(0, p // page_size + 1):
                bucket, keep = ContinuousBatcher.plan_admission_bucket(
                    p, matched, page_size, padded
                )
                matched_len = keep * page_size
                assert keep <= matched
                assert p - matched_len <= bucket, (p, matched, bucket, keep)
                assert matched_len + bucket <= padded, (p, matched, bucket, keep)
                assert bucket & (bucket - 1) == 0 or bucket == ladder_limit, (
                    p, matched, bucket,
                )


def test_warm_inserts_precompiles_every_reachable_bucket():
    """After warm_inserts(), NO admission — whatever prompt length or
    prefix-cache depth — compiles a new insert executable, and warming leaves
    engine state untouched (admissions still serve token-identically)."""
    model = _model()
    engine = ContinuousBatcher(model, num_slots=2, max_length=24, chunk_size=4, page_size=4)
    warmed = engine.warm_inserts()
    assert warmed == engine.insert_bucket_ladder() == [1, 2, 4, 8, 16, 24]
    baseline = dict(engine.trace_counts)
    rng = np.random.default_rng(3)
    system = rng.integers(1, 128, (8,)).astype(np.int32)
    rid = 0
    for trial in range(10):
        tail = rng.integers(1, 128, (int(rng.integers(1, 17)),)).astype(np.int32)
        prompt = np.concatenate([system, tail])[:20] if trial % 2 else tail
        out = engine.run([Request(rid, prompt, max_new_tokens=4)])
        reference = _static_reference(model, prompt, 4)
        np.testing.assert_array_equal(np.asarray(out[rid]), reference)
        engine.release(rid)
        rid += 1
    assert engine.trace_counts["insert"] == baseline["insert"], (
        baseline, engine.trace_counts,
    )
