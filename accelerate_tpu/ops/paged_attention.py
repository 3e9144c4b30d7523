"""Pallas TPU kernels for serving decode: paged single-query attention and the
speculative block-verify variant, with the page-table gather FUSED into the
attention walk.

The XLA read (`ops/attention._live_page_attention`) walks the LIVE pages of
all slots in fixed blocks, in one loop a layer: a turn gathers a block of K
pages and a block of V pages from the pool, reads each back once (q·K,
probs·V) and folds the block into a running softmax kept by slot — three
passes over every live page, of which the v5e's compiler keeps two in fast
memory (measured: PERF.md §5–§6). These kernels gather nothing: the grid
walks each slot's ``page_table`` directly (the table rides as a
SCALAR-PREFETCH operand, so the BlockSpec index maps pick which pool page to
stream into VMEM for each grid step) and folds every page into the shared
online-softmax accumulator (`ops/flash_common.py`), each live page read
once. Which read is faster on the chip has not been measured
(ROADMAP D13).

Page-walk contract (mirrors the engine's host-side conventions, paging.py):

  - ``page_table`` entries past a slot's reservation point at the scratch
    page (page 0). Consecutive grid steps that map to the SAME pool page skip
    the re-fetch (Pallas pipelines dedupe identical block indices), so the
    tail of a short slot's walk costs one scratch-page read, not P of them.
  - Masking is positional, not structural: query j of row i attends exactly
    ``cols <= positions[i, j]``, the same per-query mask the XLA oracle
    builds — scratch-page rows sit above every live position and contribute
    exact zeros, so prefix-shared pages, ragged lengths, and freed slots all
    come out token-identical to the gather path.
  - Rows whose every lane is masked normalize against a tiny floor
    (`finalize_softmax`), never NaN — inactive slots ride the same dispatch.

Decode and verify are ONE kernel (decode is the ``s == 1`` block): grid
``(B, pages)``, each step streaming one whole pool page — every KV head in one
DMA — and looping the heads in VMEM. GQA is handled by grouping the
``G = Hq // Hkv`` query heads of each KV head into the kernel's row axis (the
pool is shared per KV head; repeating it like the XLA path does would multiply
the very HBM traffic this kernel exists to remove). Every block's last two
dims are the operand's own, which is what the chip's compiler requires
(`tests/test_tpu_compile.py` compiles the kernel for a described v5e).

QUANTIZED pools (``k_scale``/``v_scale`` operands, `ops/quantization.py`):
int8/fp8 pages stream through the same BlockSpec walk at 1 byte/value, their
per-page-per-head scales ride ``[1, Hkv]`` blocks picked by the SAME
``tbl[b, p]`` index map, and the dequant is one fused multiply on the
VMEM-resident block before the score dot — the cache crosses HBM quantized,
fp32 exists only inside the accumulator. Token-identical to the XLA
dequantize-on-read oracle (`tests/test_quantization.py`).

Interpret mode (`interpret=None` auto-enables off-TPU) runs the same kernels
on CPU for the tier-1 parity sweeps (`tests/test_paged_kernel.py`), the
`ring_attention.py` testing pattern. All accumulation is fp32.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from .flash_common import (
    LANE,
    NEG_INF,
    finalize_softmax,
    init_softmax_state,
    online_softmax_update,
)


def _paged_kernel(
    tbl_ref, len_ref, q_ref, k_ref, v_ref, *rest,
    scale, page_size, hkv, quantized,
):
    """One (slot, page) step of the page walk, all KV heads at once.

    The page arrives as one ``[page_size, Hkv, D]`` block — the pool's own
    trailing dims, so the block satisfies the Mosaic tiling rule at any head
    count or page size and the pool needs no layout change; head ``h`` is a
    strided sublane read of it. Rows are the ``s*G`` (query position, GQA
    group) pairs of a KV head; row ``r`` attends ``cols <= limit[r]``, which
    covers single-query decode (``s == 1``) and the speculative verify block
    alike, so the speculative accept loop sees the XLA verify path's greedy
    tokens. Quantized pools thread the page's ``[1, Hkv]`` K/V scales, picked
    by the same ``tbl[b, p]`` index map that streams the page; the dequant is
    one multiply on the VMEM-resident block, so the page crosses HBM at
    int8/fp8 width and fp32 exists only inside the accumulator."""
    from jax.experimental import pallas as pl

    if quantized:
        ks_ref, vs_ref, lim_ref, o_ref, acc, m_scr, l_scr = rest
    else:
        lim_ref, o_ref, acc, m_scr, l_scr = rest

    bi = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        init_softmax_state(acc, m_scr, l_scr)

    length = len_ref[bi]  # max attend limit + 1: pages past it hold no query's keys
    base = pi * page_size

    @pl.when(base < length)
    def _step():
        limit = lim_ref[0]  # [rows, 1] int32 per-row attend limits
        for h in range(hkv):
            q = q_ref[0, h].astype(jnp.float32)  # [rows, D]
            k = k_ref[0, :, h, :].astype(jnp.float32)  # [page_size, D]
            v = v_ref[0, :, h, :].astype(jnp.float32)
            if quantized:
                k = k * ks_ref[0, :, h : h + 1]
                v = v * vs_ref[0, :, h : h + 1]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # [rows, page_size]
            cols = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols <= limit, s, NEG_INF)
            online_softmax_update(s, v, acc, m_scr, l_scr, idx=(h,))

    @pl.when(pi == pl.num_programs(1) - 1)
    def _finish():
        for h in range(hkv):
            out, _ = finalize_softmax(acc, m_scr, l_scr, idx=(h,))
            o_ref[0, h] = out.astype(o_ref.dtype)


def _paged_call(
    q, k_pool, v_pool, page_table, positions, scale, interpret,
    k_scale=None, v_scale=None,
):
    """Shared wrapper: layout transforms, prefetch grid spec, pallas_call.
    `k_scale`/`v_scale` ([num_pages, Hkv] f32 traced operands, never Python
    scalars — TPU117) switch the kernel into fused-dequant mode.

    Every block's last two dims equal the operand's own (the Mosaic tiling
    rule), so the kernel compiles for the chip at any page size / head
    count: per-slot scalars (page table, page-skip bound) ride SMEM as
    scalar-prefetch operands, everything else is a whole-trailing-dims VMEM
    block."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, hq, d = q.shape
    n_pages_pool, page_size, hkv, _ = k_pool.shape
    if hq % hkv:
        raise ValueError(f"GQA requires Hq % Hkv == 0, got {hq}, {hkv}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("quantized pools need BOTH k_scale and v_scale (or neither)")
    quantized = k_scale is not None
    if quantized:
        for name, sc in (("k_scale", k_scale), ("v_scale", v_scale)):
            if sc.shape != (n_pages_pool, hkv):
                raise ValueError(
                    f"per-page-per-head {name} must be [num_pages, Hkv] = "
                    f"{(n_pages_pool, hkv)}, got {sc.shape}"
                )
    gsize = hq // hkv
    rows = s * gsize
    pages_per_slot = page_table.shape[-1]

    # [B, s, Hq, D] -> [B, Hkv, s*G, D]: query head h*G+g rides kv head h's
    # walk; row j*G+g of a head carries query position j's attend limit.
    qt = (
        q.reshape(b, s, hkv, gsize, d)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, hkv, rows, d)
    )
    table = jnp.clip(jnp.asarray(page_table, jnp.int32), 0, n_pages_pool - 1)
    pos = jnp.asarray(positions, jnp.int32).reshape(b, s)
    limits = jnp.repeat(pos, gsize, axis=1)[:, :, None]  # [B, rows, 1]
    lengths = jnp.max(pos, axis=1) + 1  # [B] scalar page-skip bound per slot

    kernel = functools.partial(
        _paged_kernel, scale=float(scale), page_size=page_size, hkv=hkv,
        quantized=quantized,
    )
    q_spec = pl.BlockSpec((1, hkv, rows, d), lambda bi, pi, tbl, ln: (bi, 0, 0, 0))
    # THE fused page-table gather: grid step (b, p) streams pool page
    # table[b, p], every KV head in one DMA. Table entries past a slot's
    # reservation are the scratch page — identical consecutive block indices,
    # which the Pallas pipeline fetches once, not P times.
    page_spec = pl.BlockSpec(
        (1, page_size, hkv, d), lambda bi, pi, tbl, ln: (tbl[bi, pi], 0, 0, 0)
    )
    in_specs = [q_spec, page_spec, page_spec]
    operands = [qt, k_pool, v_pool]
    if quantized:
        # The streamed page's per-head scales ride the SAME tbl[b, p] walk as
        # the page itself — the dequant is fused, not a second gather.
        scale_spec = pl.BlockSpec((1, 1, hkv), lambda bi, pi, tbl, ln: (tbl[bi, pi], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale[:, None, :], v_scale[:, None, :]]
    in_specs.append(pl.BlockSpec((1, rows, 1), lambda bi, pi, tbl, ln: (bi, 0, 0)))
    operands.append(limits)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pages_per_slot),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hkv, rows, d), jnp.float32),
            pltpu.VMEM((hkv, rows, LANE), jnp.float32),
            pltpu.VMEM((hkv, rows, LANE), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="paged_attention",
    )(table, lengths, *operands)
    return (
        out.reshape(b, hkv, s, gsize, d).transpose(0, 2, 1, 3, 4).reshape(b, s, hq, d)
    )


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def paged_decode_attention(
    q, k_pool, v_pool, page_table, positions, *, scale=None, interpret=None,
    k_scale=None, v_scale=None,
):
    """Single-query paged decode attention over a pool-resident KV cache.

    Args:
        q: [B, 1, Hq, D] this step's queries (one per slot).
        k_pool / v_pool: [num_pages, page_size, Hkv, D] page pools, ALREADY
            holding this dispatch's K/V writes (the caller scatters first —
            query i attends its own new row via ``cols <= positions[i]``).
        page_table: [B, pages_per_slot] int32 pool-page ids (traced operand);
            unused entries point at the scratch page.
        positions: [B, 1] (or [B]) int32 — row i attends ``cols <= positions[i]``.
        scale: defaults to 1/sqrt(D).
        interpret: None = auto (Pallas interpreter off-TPU, compiled on TPU).
        k_scale / v_scale: [num_pages, Hkv] f32 per-page-per-head scale pools
            for int8/fp8 page pools (traced operands, never Python scalars —
            TPU117); the dequant fuses into the page-streaming loop. Both or
            neither.

    Returns [B, 1, Hq, D], token-identical to the XLA gather oracle
    (dequantize-on-read for quantized pools).
    """
    if q.ndim != 4 or q.shape[1] != 1:
        raise ValueError(f"paged_decode_attention takes [B, 1, Hq, D] queries, got {q.shape}")
    return paged_verify_attention(
        q, k_pool, v_pool, page_table, positions, scale=scale, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale,
    )


def paged_verify_attention(
    q, k_pool, v_pool, page_table, positions, *, scale=None, interpret=None,
    k_scale=None, v_scale=None,
):
    """Block-verify paged attention: the [B, s] multi-token variant used by
    speculative decoding's verify step (s = draft_tokens + 1).

    Args:
        q: [B, s, Hq, D] the block's queries.
        k_pool / v_pool / page_table: as `paged_decode_attention` — the pools
            already hold the block's K/V writes.
        positions: [B, s] int32 — query j of row i attends
            ``cols <= positions[i, j]`` (its accepted prefix plus the block
            tokens at or before it, all written by this same dispatch).
        k_scale / v_scale: as `paged_decode_attention` (quantized pools).

    Returns [B, s, Hq, D].
    """
    if q.ndim != 4:
        raise ValueError(f"paged_verify_attention takes [B, s, Hq, D] queries, got {q.shape}")
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])

    return _paged_call(
        q, k_pool, v_pool, page_table, positions, scale, _auto_interpret(interpret),
        k_scale=k_scale, v_scale=v_scale,
    )
