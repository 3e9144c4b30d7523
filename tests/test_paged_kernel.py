"""Pallas paged-decode & block-verify kernel pins (`ops/paged_attention.py`).

CPU tier-1 coverage via Pallas interpret mode at tiny shapes (the
`ring_attention.py` pattern): the kernels that fuse the page-table gather into
the serving hot loop are pinned against the XLA gather oracle —
kernel==oracle numerics per dtype (f32 tight, bf16 tolerance-bounded), greedy
token parity through `serving.ContinuousBatcher` across page sizes / ragged
cache lengths / prefix-shared pages / speculative draft blocks, scratch-page
rows contributing exact zeros, and the decode-compiled-once discipline with
the kernel on the decode path.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models.llama import LlamaConfig, create_llama_model
from accelerate_tpu.ops.paged_attention import (
    paged_decode_attention,
    paged_verify_attention,
)
from accelerate_tpu.serving import ContinuousBatcher, Request

pytestmark = pytest.mark.kernels


# ----------------------------------------------------------------- kernel-level
def _random_pool(rng, num_pages, page_size, hkv, d, dtype=np.float32):
    k = rng.normal(size=(num_pages, page_size, hkv, d)).astype(dtype)
    v = rng.normal(size=(num_pages, page_size, hkv, d)).astype(dtype)
    return k, v


def _oracle(q, pool_k, pool_v, table, positions):
    """The XLA gather path, re-derived in numpy/f64-free f32: gather the
    slot's pages into logical order, repeat KV heads for GQA, mask
    ``cols <= positions[i, j]``, exact two-pass softmax."""
    b, s, hq, d = q.shape
    ps = pool_k.shape[1]
    hkv = pool_k.shape[2]
    L = table.shape[1] * ps
    kf = pool_k[table].reshape(b, L, hkv, d).astype(np.float32)
    vf = pool_v[table].reshape(b, L, hkv, d).astype(np.float32)
    reps = hq // hkv
    kf, vf = np.repeat(kf, reps, axis=2), np.repeat(vf, reps, axis=2)
    scores = np.einsum("bqhd,bkhd->bhqk", q.astype(np.float32), kf) / np.sqrt(d)
    cols = np.arange(L)[None, None, None, :]
    scores = np.where(cols <= positions[:, None, :, None], scores, -1e30)
    scores -= scores.max(-1, keepdims=True)
    probs = np.exp(scores)
    probs /= probs.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", probs, vf)


@pytest.mark.parametrize("page_size", [4, 8, 16])
def test_decode_kernel_matches_oracle_f32(page_size):
    """Single-query paged decode vs the gather oracle across page sizes and
    ragged cache lengths (first position, page boundaries, full window)."""
    rng = np.random.default_rng(0)
    B, Hq, Hkv, D, P = 4, 4, 2, 8, 3
    N = B * P + 1
    pool_k, pool_v = _random_pool(rng, N, page_size, Hkv, D)
    table = np.arange(1, N).reshape(B, P).astype(np.int32)
    L = P * page_size
    # Ragged lengths: pos 0 (one valid cell), a page-boundary-1, mid, full.
    pos = np.array([[0], [page_size - 1], [L // 2], [L - 1]], np.int32)
    q = rng.normal(size=(B, 1, Hq, D)).astype(np.float32)
    out = np.asarray(
        paged_decode_attention(
            jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(table), jnp.asarray(pos),
        )
    )
    np.testing.assert_allclose(out, _oracle(q, pool_k, pool_v, table, pos), atol=2e-5)


def test_decode_kernel_bf16_within_tolerance():
    rng = np.random.default_rng(1)
    B, Hq, Hkv, D, P, page_size = 3, 4, 2, 8, 3, 4
    N = B * P + 1
    pool_k, pool_v = _random_pool(rng, N, page_size, Hkv, D)
    table = np.arange(1, N).reshape(B, P).astype(np.int32)
    pos = np.array([[3], [7], [11]], np.int32)
    q = rng.normal(size=(B, 1, Hq, D)).astype(np.float32)
    out = np.asarray(
        paged_decode_attention(
            jnp.asarray(q, jnp.bfloat16),
            jnp.asarray(pool_k, jnp.bfloat16),
            jnp.asarray(pool_v, jnp.bfloat16),
            jnp.asarray(table), jnp.asarray(pos),
        ).astype(jnp.float32)
    )
    expect = _oracle(q, pool_k, pool_v, table, pos)
    # bf16 inputs: ~7 bits of mantissa on the operands; accumulation is f32.
    np.testing.assert_allclose(out, expect, atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("s", [2, 4, 5])
def test_verify_kernel_matches_oracle(s):
    """Block-verify (the speculative [B, s] variant): per-query
    ``cols <= positions[i, j]`` masks across draft-block widths."""
    rng = np.random.default_rng(2)
    B, Hq, Hkv, D, P, page_size = 3, 4, 2, 8, 4, 4
    N = B * P + 1
    pool_k, pool_v = _random_pool(rng, N, page_size, Hkv, D)
    table = np.arange(1, N).reshape(B, P).astype(np.int32)
    base = np.array([0, 5, 9], np.int32)
    pos = base[:, None] + np.arange(s)[None, :].astype(np.int32)
    q = rng.normal(size=(B, s, Hq, D)).astype(np.float32)
    out = np.asarray(
        paged_verify_attention(
            jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(table), jnp.asarray(pos),
        )
    )
    np.testing.assert_allclose(out, _oracle(q, pool_k, pool_v, table, pos), atol=2e-5)


def test_mha_shape_no_gqa_grouping():
    """Hq == Hkv (the gpt_neox shape, G = 1) walks the same kernel."""
    rng = np.random.default_rng(3)
    B, H, D, P, page_size = 2, 4, 8, 2, 4
    N = B * P + 1
    pool_k, pool_v = _random_pool(rng, N, page_size, H, D)
    table = np.arange(1, N).reshape(B, P).astype(np.int32)
    pos = np.array([[2], [6]], np.int32)
    q = rng.normal(size=(B, 1, H, D)).astype(np.float32)
    out = np.asarray(
        paged_decode_attention(
            jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(table), jnp.asarray(pos),
        )
    )
    np.testing.assert_allclose(out, _oracle(q, pool_k, pool_v, table, pos), atol=2e-5)


def test_scratch_page_rows_contribute_zero():
    """Poison the scratch page (page 0) with huge values: outputs must not
    move — table entries past a slot's reservation point at page 0, and the
    positional mask keeps every scratch cell invisible."""
    rng = np.random.default_rng(4)
    B, Hq, Hkv, D, P, page_size = 2, 4, 2, 8, 4, 4
    N = 6
    pool_k, pool_v = _random_pool(rng, N, page_size, Hkv, D)
    # Short slots: trailing table entries at the scratch page.
    table = np.array([[1, 2, 0, 0], [3, 0, 0, 0]], np.int32)
    pos = np.array([[6], [2]], np.int32)
    q = rng.normal(size=(B, 1, Hq, D)).astype(np.float32)

    def run(pk, pv):
        return np.asarray(
            paged_decode_attention(
                jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
                jnp.asarray(table), jnp.asarray(pos),
            )
        )

    clean = run(pool_k, pool_v)
    poisoned_k, poisoned_v = pool_k.copy(), pool_v.copy()
    poisoned_k[0] = 1e4
    poisoned_v[0] = 1e4
    np.testing.assert_array_equal(clean, run(poisoned_k, poisoned_v))


def test_prefix_shared_pages_read_identically():
    """Two slots whose tables share the same head pages (the prefix cache's
    layout) must each read the shared content exactly as if it were private."""
    rng = np.random.default_rng(5)
    Hq, Hkv, D, P, page_size = 4, 2, 8, 3, 4
    N = 8
    pool_k, pool_v = _random_pool(rng, N, page_size, Hkv, D)
    # Rows share pages 1-2 (a cached system prompt), then diverge.
    table = np.array([[1, 2, 3], [1, 2, 4]], np.int32)
    pos = np.array([[10], [11]], np.int32)
    q = rng.normal(size=(2, 1, Hq, D)).astype(np.float32)
    out = np.asarray(
        paged_decode_attention(
            jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(table), jnp.asarray(pos),
        )
    )
    np.testing.assert_allclose(out, _oracle(q, pool_k, pool_v, table, pos), atol=2e-5)


# ---------------------------------------------------------------- the page walk
# What the walk of runs newly depends on (ops/paged_attention.py): 5 slots x 6
# pages of 4 tokens, a run of 2 pages, so a full slot is 3 entries. Each case
# is the LAST position a slot's queries attend (0: an idle slot, one entry).
WALK_PAGES, WALK_PAGE_SIZE, WALK_RUN = 6, 4, 2
_WALKS = {
    "ragged_with_idle_slots_between": [13, 0, 22, 0, 5],  # 2 + 1 + 3 + 1 + 1 entries
    "live_pages_end_inside_a_run": [9, 17, 1, 10, 19],  # 3, 5, 1, 3, 5 live pages: the last run holds one
    "every_slot_at_the_full_window": [23, 23, 23, 23, 23],
    "every_slot_idle": [0, 0, 0, 0, 0],
    "list_ends_on_a_buffer_pair": [15, 7, 16, 0, 8],  # 2 + 1 + 3 + 1 + 2 = 8 entries: both buffers end used
    "list_ends_one_entry_past_it": [16, 7, 16, 0, 8],  # 9 entries
    "shared_prefix_pages": [14, 18, 21, 0, 3],
}


def _walk_operands(walk, s, hq, hkv, dtype, d=8, seed=11):
    rng = np.random.default_rng(seed)
    slots = len(_WALKS[walk])
    num_pages = slots * WALK_PAGES + 1
    pool_k, pool_v = _random_pool(rng, num_pages, WALK_PAGE_SIZE, hkv, d)
    table = rng.permutation(np.arange(1, num_pages)).reshape(slots, WALK_PAGES).astype(np.int32)
    if walk == "shared_prefix_pages":
        table[1, :3] = table[0, :3]  # a cached system prompt in two slots' tables, one of them
        table[2, :2] = table[0, :2]  # reading on past it into pages of its own
    top = np.asarray(_WALKS[walk], np.int32)
    # a verify block's queries sit at consecutive positions ending at `top` (an idle slot's from 0)
    base = np.maximum(top - (s - 1), 0)
    pos = (base[:, None] + np.arange(s)[None, :]).astype(np.int32)
    q = rng.normal(size=(slots, s, hq, d)).astype(np.float32)
    return jnp.asarray(q, dtype), pool_k, pool_v, jnp.asarray(table), jnp.asarray(pos)


def _both_reads(monkeypatch, q, pool_k, pool_v, table, pos, scales=None):
    """The kernel and `_live_page_attention` on the same operands, the kernel
    in runs of `WALK_RUN` pages and the XLA loop in blocks of three."""
    from accelerate_tpu.ops import attention

    itemsize = q.dtype.itemsize
    page_bytes = WALK_PAGE_SIZE * pool_k.shape[2] * pool_k.shape[3] * itemsize
    monkeypatch.setattr(attention, "_KERNEL_RUN_BYTES", WALK_RUN * page_bytes)
    monkeypatch.setattr(attention, "_READ_BLOCK_BYTES", 3 * page_bytes)
    k_scale, v_scale = scales if scales is not None else (None, None)
    got = paged_verify_attention(q, pool_k, pool_v, table, pos, k_scale=k_scale, v_scale=v_scale)
    want = attention._live_page_attention(q, pool_k, pool_v, pos, table, scales)
    return np.asarray(got.astype(jnp.float32)), np.asarray(want.astype(jnp.float32))


@pytest.mark.parametrize("s", [1, 5], ids=["decode", "verify5"])
@pytest.mark.parametrize("walk", list(_WALKS))
def test_walk_of_runs_matches_the_xla_read(monkeypatch, walk, s):
    """Every shape of live list the walk meets, f32, multi-head: the kernel's
    output is the XLA read's on the same pools, table and positions."""
    q, pool_k, pool_v, table, pos = _walk_operands(walk, s, 4, 4, jnp.float32)
    got, want = _both_reads(monkeypatch, q, jnp.asarray(pool_k), jnp.asarray(pool_v), table, pos)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # and both are the gather oracle's
    np.testing.assert_allclose(
        got, _oracle(np.asarray(q), pool_k, pool_v, np.asarray(table), np.asarray(pos)), atol=2e-5
    )


@pytest.mark.parametrize("s", [1, 5], ids=["decode", "verify5"])
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("pool", ["bf16", "int8", "fp8_e4m3"])
def test_walk_of_runs_by_pool_dtype_and_group(monkeypatch, pool, group, s):
    """The ragged walk again with the pool stored in bf16 (bf16 products, fp32
    accumulation: bf16 tolerance), int8 and fp8 (scales applied to scores and
    probabilities; fp32 queries: tight), one and four query heads a KV head."""
    from accelerate_tpu.ops.quantization import kv_quant_spec, quantize_kv_pages

    hkv = 2
    dtype = jnp.bfloat16 if pool == "bf16" else jnp.float32
    q, pool_k, pool_v, table, pos = _walk_operands(
        "ragged_with_idle_slots_between", s, hkv * group, hkv, dtype
    )
    if pool == "bf16":
        got, want = _both_reads(
            monkeypatch, q, jnp.asarray(pool_k, dtype), jnp.asarray(pool_v, dtype), table, pos
        )
        np.testing.assert_allclose(got, want, atol=3e-2, rtol=3e-2)
        return
    spec = kv_quant_spec(pool)
    kq, ks = quantize_kv_pages(jnp.asarray(pool_k), spec)
    vq, vs = quantize_kv_pages(jnp.asarray(pool_v), spec)
    got, want = _both_reads(monkeypatch, q, kq, vq, table, pos, scales=(ks, vs))
    np.testing.assert_allclose(got, want, atol=5e-5)


@pytest.mark.parametrize(
    "pool,hkv,d,staged",
    [
        # whole tiles — D whole 128-lane rows, heads whole packed sublanes: read in place
        ("f32", 1, 128, False), ("bf16", 2, 128, False), ("int8", 4, 128, False), ("fp8_e4m3", 4, 128, False),
        # anything else is padded to that first: llama-1b's heads of 64, one KV head
        # of bf16, two of int8, and a shape that is short on both axes
        ("bf16", 2, 64, True), ("bf16", 1, 128, True), ("int8", 2, 128, True), ("fp8_e4m3", 3, 96, True),
    ],
)
@pytest.mark.parametrize("s", [1, 5], ids=["decode", "verify5"])
def test_walk_reads_a_pool_in_place_or_staged(monkeypatch, s, pool, hkv, d, staged):
    """The ragged walk with two query heads a KV head over pools the kernel
    copies pages out of as they are and pools it must stage
    (`ops.attention.kernel_stages_pool`: zero lanes, zero heads): the XLA
    read's output either way, and the staged pool is the only one padded."""
    from accelerate_tpu.ops import attention, paged_attention
    from accelerate_tpu.ops.quantization import kv_quant_spec, quantize_kv_pages

    dtype = jnp.bfloat16 if pool == "bf16" else jnp.float32
    q, pool_k, pool_v, table, pos = _walk_operands(
        "ragged_with_idle_slots_between", s, 2 * hkv, hkv, dtype, d=d
    )
    assert attention.kernel_stages_pool(hkv, d, {"f32": 4, "bf16": 2}.get(pool, 1)) == staged
    padded, pad = [], jnp.pad
    monkeypatch.setattr(paged_attention.jnp, "pad", lambda x, widths: padded.append(x.shape) or pad(x, widths))
    if pool in ("f32", "bf16"):
        got, want = _both_reads(monkeypatch, q, jnp.asarray(pool_k, dtype), jnp.asarray(pool_v, dtype), table, pos)
        tol = dict(atol=3e-2, rtol=3e-2) if pool == "bf16" else dict(atol=2e-5)
    else:
        spec = kv_quant_spec(pool)
        kq, ks = quantize_kv_pages(jnp.asarray(pool_k), spec)
        vq, vs = quantize_kv_pages(jnp.asarray(pool_v), spec)
        got, want = _both_reads(monkeypatch, q, kq, vq, table, pos, scales=(ks, vs))
        tol = dict(atol=5e-5)
    np.testing.assert_allclose(got, want, **tol)
    assert (pool_k.shape in padded) == staged


# ------------------------------------------------------------- a latent pool
# ONE pool of rows, no head axis, keys and values both (`v_pool=None`; MLA's
# absorbed form): the same walk over 5 slots x 6 pages (of 8 rows: a tile of
# float32, which the kernel copies in place), a run of 2 pages in pieces of
# one (the products run over one piece or two, whichever hold a live position;
# a piece's pages are copied as a group), against `_live_page_attention` on
# the same operands. A row is 128 values of which the first 96 are its
# values, and the softmax scale is the family's own, not 1 / sqrt(row).
LATENT_ROW, LATENT_VALUES, LATENT_SCALE = 128, 96, 0.05


def _latent_reads(monkeypatch, walk, s, dtype, page_size=8, row=LATENT_ROW, value_dim=LATENT_VALUES,
                  run=WALK_RUN, piece=1, hq=4):
    from accelerate_tpu.ops import attention

    rng = np.random.default_rng(17)
    top = np.asarray(_WALKS[walk], np.int32) * page_size // WALK_PAGE_SIZE  # the same pages live at any page size
    slots, num_pages = len(top), len(top) * WALK_PAGES + 1
    pool = jnp.asarray(rng.normal(size=(num_pages, page_size, row)).astype(np.float32), dtype)
    table = rng.permutation(np.arange(1, num_pages)).reshape(slots, WALK_PAGES).astype(np.int32)
    if walk == "shared_prefix_pages":
        table[1, :3] = table[0, :3]
        table[2, :2] = table[0, :2]
    pos = jnp.asarray(np.maximum(top - (s - 1), 0)[:, None] + np.arange(s)[None, :], jnp.int32)
    q = jnp.asarray(rng.normal(size=(slots, s, hq, row)).astype(np.float32), dtype)
    page_bytes = page_size * row * q.dtype.itemsize
    monkeypatch.setattr(attention, "_KERNEL_RUN_BYTES", run * page_bytes // 2)  # a latent run is a K run and a V run
    monkeypatch.setattr(attention, "_KERNEL_PIECE_TOKENS", piece * page_size)
    monkeypatch.setattr(attention, "_READ_BLOCK_BYTES", 3 * page_bytes)
    assert attention.kernel_run_pages(WALK_PAGES, page_size, 1, row, q.dtype.itemsize, latent=True) == run
    table = jnp.asarray(table)
    got = paged_verify_attention(q, pool, None, table, pos, scale=LATENT_SCALE, value_dim=value_dim)
    want = attention._live_page_attention(q, pool, None, pos, table, None, scale=LATENT_SCALE, value_dim=value_dim)
    assert got.shape == want.shape == (slots, s, hq, row if value_dim is None else value_dim)
    return np.asarray(got.astype(jnp.float32)), np.asarray(want.astype(jnp.float32))


@pytest.mark.parametrize("s", [1, 5], ids=["decode", "verify5"])
@pytest.mark.parametrize("walk", list(_WALKS))
def test_latent_walk_matches_the_xla_read(monkeypatch, walk, s):
    """Every shape of live list again, over a pool of latent rows: ragged
    positions, idle slots, every page live, a last run of one page, shared
    prefix pages, lists that end on either buffer — the XLA read's output,
    the rope columns (96..127) scored and never summed into it."""
    got, want = _latent_reads(monkeypatch, walk, s, jnp.float32)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize(
    "dtype,page_size,row,value_dim,run,piece",
    [
        ("f32", 8, 128, 128, 2, 1),  # every column a value
        ("f32", 8, 256, 128, 2, 2),  # the values whole lane tiles of a wider row (kimi's 512 of 640); a piece the run
        ("f32", 8, 256, None, 3, 1),  # no `value_dim`: the whole row comes back
        ("f32", 8, 128, 40, 4, 2),  # runs of two pieces; a last run shorter than a piece's pages
        ("f32", 8, 128, 40, 6, 4),  # the window one run of a piece and a half: rounded down to one piece
        ("bf16", 16, 256, 128, 2, 1),  # bf16 rows in pages of whole packed sublanes: bf16 tolerance
        ("bf16", 16, 128, 96, 4, 2),
    ],
)
@pytest.mark.parametrize("s", [1, 5], ids=["decode", "verify5"])
def test_latent_walk_by_row_values_run_and_piece(monkeypatch, s, dtype, page_size, row, value_dim, run, piece):
    """The ragged walk over latent pools by what the kernel's program depends
    on: the row's width against its values', the pages a run and a piece of it
    hold (`kernel_run_pages` makes a run whole pieces), the pool's dtype."""
    from accelerate_tpu.ops import attention

    kept = run // piece * piece  # what `kernel_run_pages` keeps of a run of more than a piece
    got, want = _latent_reads(monkeypatch, "live_pages_end_inside_a_run", s, jnp.bfloat16 if dtype == "bf16" else jnp.float32,
                              page_size=page_size, row=row, value_dim=value_dim, run=kept, piece=piece)
    monkeypatch.setattr(attention, "_KERNEL_RUN_BYTES", run * page_size * row * (2 if dtype == "bf16" else 4) // 2)
    assert attention.kernel_run_pages(WALK_PAGES, page_size, 1, row, 2 if dtype == "bf16" else 4, latent=True) == kept
    np.testing.assert_allclose(got, want, **(dict(atol=3e-2, rtol=3e-2) if dtype == "bf16" else dict(atol=2e-5)))


def test_latent_scratch_page_rows_contribute_zero(monkeypatch):
    """Poison the scratch page and every page no table row names: the latent
    read does not move — pages past a slot's live ones are never copied, and
    rows of a live page past the slot's position are masked."""
    from accelerate_tpu.ops import attention

    rng = np.random.default_rng(23)
    pool = rng.normal(size=(9, 8, 128)).astype(np.float32)
    table = jnp.asarray([[1, 2, 0, 0], [3, 0, 0, 0]], jnp.int32)
    pos = jnp.asarray([[11], [2]], jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, 1, 4, 128)).astype(np.float32))
    monkeypatch.setattr(attention, "_KERNEL_RUN_BYTES", 8 * 128 * 4)  # runs of 2 pages

    def read(pool):
        return np.asarray(paged_decode_attention(q, jnp.asarray(pool), None, table, pos, scale=0.2, value_dim=64))

    clean = read(pool)
    poisoned = pool.copy()
    poisoned[[0, 4, 5, 6, 7, 8]] = 1e4
    poisoned[2, 4:] = 1e4  # slot 0 attends rows 0..3 of its second page
    poisoned[3, 3:] = 1e4
    np.testing.assert_array_equal(clean, read(poisoned))


def test_latent_pool_the_kernel_cannot_read_in_place_is_refused_not_staged():
    from accelerate_tpu.ops import attention

    assert attention.kernel_refuses_rows(16, 640, 2) is None and attention.kernel_refuses_rows(8, 128, 4) is None
    assert attention.kernel_refuses_rows(16, 576, 2) and attention.kernel_refuses_rows(8, 640, 2)
    q, table, pos = jnp.zeros((2, 1, 4, 96)), jnp.zeros((2, 3), jnp.int32), jnp.zeros((2, 1), jnp.int32)
    with pytest.raises(ValueError, match="rows of 96 values.*never staged"):
        paged_decode_attention(q, jnp.zeros((7, 8, 96)), None, table, pos, scale=0.2, value_dim=32)


@pytest.mark.parametrize("run_pages", [2, 3])
def test_engine_greedy_token_parity_in_runs_of_pages(monkeypatch, run_pages):
    """Greedy decode through `ContinuousBatcher` with a slot's 8 pages walked
    in runs of 2 and of 3 (the last run then holds 2): token-identical to the
    XLA read, one decode program, and `read_blocks` counts the kernel's
    entries."""
    from accelerate_tpu.ops import attention
    from accelerate_tpu.telemetry.flight_recorder import FlightRecorder
    from accelerate_tpu.telemetry.tracing import Tracer

    model = create_llama_model(_tiny_config(), seq_len=32)
    cfg = model.module.config
    page_bytes = 4 * cfg.num_key_value_heads * cfg.head_dim * 4
    monkeypatch.setattr(attention, "_KERNEL_RUN_BYTES", run_pages * page_bytes)
    requests = _mixed_requests(np.random.default_rng(12), 5, prompt_lo=6, prompt_hi=22)
    common = dict(num_slots=3, max_length=32, chunk_size=4, page_size=4)
    _, xla_tokens = _run_engine(model, requests, attention_impl="xla", **common)
    recorder = FlightRecorder()
    engine, kernel_tokens = _run_engine(
        model, requests, attention_impl="pallas_paged",
        tracer=Tracer(recorder=recorder, category="serve"), **common,
    )
    assert kernel_tokens == xla_tokens
    assert engine.trace_counts["decode_chunk"] == 1
    chunks = [r["attrs"] for r in recorder.records() if r["name"] == "serve.decode_chunk"]
    assert chunks and all(c["read_impl"] == "pallas_paged" for c in chunks)
    # three slots, each 1..ceil(8 / run) entries
    assert all(3 <= c["read_blocks"] <= 3 * -(-8 // run_pages) for c in chunks)
    assert max(c["read_blocks"] for c in chunks) > 3  # some slot was read in more than one run


# -------------------------------------------------------------- program-level
def _tiny_config(**overrides):
    base = dict(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=64,
        rope_theta=10000.0,
    )
    base.update(overrides)
    return LlamaConfig(**base)


def test_verify_program_kernel_matches_xla():
    """`make_causal_programs(verify_block=True)` built over two module
    variants that differ ONLY in `decode_attention_impl`: scoring the same
    token block through the same page tables must produce matching [B, s, V]
    logits (and identical argmax — the token the accept loop consumes)."""
    import dataclasses

    from accelerate_tpu.generation import make_causal_programs

    model = create_llama_model(_tiny_config(), seq_len=16)
    num_pages = 9
    step_cfg = dataclasses.replace(
        model.module.config, decode_cache_length=16, decode_slot_cache=True,
        decode_page_size=4, decode_num_pages=num_pages,
    )
    rng = np.random.default_rng(6)
    B, s = 2, 3
    tokens = jnp.asarray(rng.integers(1, 128, (B, s)), jnp.int32)
    positions = jnp.asarray(np.broadcast_to(np.arange(s), (B, s)), jnp.int32)
    table = jnp.asarray(np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32))
    params = model.params if "params" in model.params else {"params": model.params}
    logits = {}
    for impl in ("xla", "pallas_paged"):
        module = type(model.module)(
            dataclasses.replace(step_cfg, decode_attention_impl=impl)
        )
        _, _, verify = make_causal_programs(
            module, lambda p: p, step_mask_operand=True, verify_block=True
        )
        cache = jax.tree_util.tree_map(
            lambda leaf: jnp.zeros(leaf.shape, leaf.dtype),
            jax.eval_shape(
                lambda p: module.apply(
                    p, tokens, table, positions, mutable=["cache"]
                )[1]["cache"],
                params,
            ),
        )
        out, _cache = jax.jit(verify)(params, cache, tokens, positions, table)
        logits[impl] = np.asarray(out)
    np.testing.assert_allclose(logits["xla"], logits["pallas_paged"], atol=2e-4)
    np.testing.assert_array_equal(
        logits["xla"].argmax(-1), logits["pallas_paged"].argmax(-1)
    )


# --------------------------------------------------------------- engine-level
def _mixed_requests(rng, n, vocab=128, prompt_lo=3, prompt_hi=20, new_lo=2, new_hi=10):
    return [
        Request(
            i,
            rng.integers(1, vocab, (int(rng.integers(prompt_lo, prompt_hi)),)).astype(np.int32),
            max_new_tokens=int(rng.integers(new_lo, new_hi)),
        )
        for i in range(n)
    ]


def _run_engine(model, requests, **kwargs):
    engine = ContinuousBatcher(model, max_queue=len(requests) + 2, **kwargs)
    results = engine.run(
        [Request(r.request_id, r.input_ids, max_new_tokens=r.max_new_tokens) for r in requests]
    )
    return engine, {rid: list(map(int, toks)) for rid, toks in results.items()}


@pytest.mark.parametrize("page_size", [4, 8, 16])
def test_engine_greedy_token_parity_across_page_sizes(page_size):
    """The serving pin: greedy outputs through `ContinuousBatcher` are
    token-IDENTICAL (f32) between the kernel path and the XLA oracle, across
    page sizes and ragged prompt/budget mixes — and the kernel-path decode
    still compiles exactly once across mixed admissions."""
    model = create_llama_model(_tiny_config(), seq_len=32)
    rng = np.random.default_rng(7)
    requests = _mixed_requests(rng, 6)
    common = dict(num_slots=2, max_length=64, chunk_size=4, page_size=page_size)
    _, xla_tokens = _run_engine(model, requests, attention_impl="xla", **common)
    engine, kernel_tokens = _run_engine(
        model, requests, attention_impl="pallas_paged", **common
    )
    assert kernel_tokens == xla_tokens
    assert engine.trace_counts["decode_chunk"] == 1
    assert engine.attention_impl == "pallas_paged"
    assert engine.stats["attention_impl"] == "pallas_paged"


def test_engine_parity_with_prefix_cache_hits():
    """Prefix-shared pages on the kernel path: the second wave of requests
    reuses the first wave's registered system-prompt pages (prefix hits > 0)
    and still matches the oracle token-for-token."""
    model = create_llama_model(_tiny_config(), seq_len=32)
    rng = np.random.default_rng(8)
    system = rng.integers(1, 128, (9,)).astype(np.int32)
    # Two waves over the same shared system prompt: wave 1 registers its
    # pages, wave 2 hits them. Prompts fixed up front so both impls serve
    # byte-identical traffic.
    waves = [
        [
            np.concatenate([system, rng.integers(1, 128, (3 + i,)).astype(np.int32)])
            for i in range(4)
        ]
        for _ in range(2)
    ]
    tokens = {}
    engines = {}
    for impl in ("xla", "pallas_paged"):
        engine = ContinuousBatcher(
            model, num_slots=2, max_length=64, chunk_size=4, page_size=4,
            attention_impl=impl, max_queue=16,
        )
        out = {}
        for w, prompts in enumerate(waves):
            out.update(
                engine.run(
                    [Request(w * 4 + i, p, max_new_tokens=5) for i, p in enumerate(prompts)]
                )
            )
        tokens[impl] = {k: list(map(int, v)) for k, v in out.items()}
        engines[impl] = engine
    assert tokens["pallas_paged"] == tokens["xla"]
    stats = engines["pallas_paged"].stats
    assert stats["prefix_cache"]["hits"] > 0, "prefix path never exercised"
    assert engines["pallas_paged"].trace_counts["decode_chunk"] == 1


def test_engine_parity_speculative_draft_blocks():
    """Speculative decoding through the block-verify KERNEL: spec-on kernel
    == spec-on oracle == spec-off kernel, token for token (the accept loop's
    greedy property survives the kernel swap), with drafts really accepted."""
    model = create_llama_model(_tiny_config(), seq_len=32)
    rng = np.random.default_rng(9)
    motif = rng.integers(1, 128, (5,))
    prompts = [
        np.tile(motif, 4).astype(np.int32)[: int(rng.integers(8, 16))] for _ in range(4)
    ]
    reqs = lambda: [Request(i, p, max_new_tokens=8) for i, p in enumerate(prompts)]
    runs = {}
    for label, kwargs in {
        "spec_kernel": dict(speculative=True, draft_tokens=3, attention_impl="pallas_paged"),
        "spec_xla": dict(speculative=True, draft_tokens=3, attention_impl="xla"),
        "plain_kernel": dict(attention_impl="pallas_paged"),
    }.items():
        engine = ContinuousBatcher(
            model, num_slots=2, max_length=64, chunk_size=3, page_size=4,
            max_queue=8, **kwargs,
        )
        runs[label] = {
            rid: list(map(int, toks)) for rid, toks in engine.run(reqs()).items()
        }
        if label == "spec_kernel":
            spec = engine.stats["speculative"]
            assert spec["verify_steps"] > 0
            assert engine.trace_counts["decode_chunk"] == 1
    assert runs["spec_kernel"] == runs["spec_xla"] == runs["plain_kernel"]


def test_engine_parity_gpt_neox():
    """The second slot-cache family (Hq == Hkv, partial rotary) through the
    kernel path: greedy token parity with its own oracle."""
    from accelerate_tpu.models.gpt_neox import GPTNeoXConfig, create_gpt_neox_model

    cfg = GPTNeoXConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
        num_attention_heads=4, rotary_pct=0.5, max_position_embeddings=64,
    )
    model = create_gpt_neox_model(cfg, seq_len=16)
    rng = np.random.default_rng(10)
    requests = _mixed_requests(rng, 4, prompt_hi=12, new_hi=6)
    common = dict(num_slots=2, max_length=32, chunk_size=4, page_size=4)
    _, xla_tokens = _run_engine(model, requests, attention_impl="xla", **common)
    engine, kernel_tokens = _run_engine(
        model, requests, attention_impl="pallas_paged", **common
    )
    assert kernel_tokens == xla_tokens
    assert engine.trace_counts["decode_chunk"] == 1


# ------------------------------------------------------------------ guardrails
def test_pallas_paged_requires_paged_cache():
    model = create_llama_model(_tiny_config(), seq_len=16)
    with pytest.raises(ValueError, match="contiguous per-slot KV layout is gone"):
        ContinuousBatcher(
            model, num_slots=2, max_length=32, paged=False,
            attention_impl="pallas_paged", max_queue=4,
        )
    with pytest.raises(ValueError, match="attention_impl"):
        ContinuousBatcher(
            model, num_slots=2, max_length=32, attention_impl="mosaic", max_queue=4
        )
