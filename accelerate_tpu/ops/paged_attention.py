"""Pallas TPU kernel for serving decode: paged single-query attention and the
speculative block-verify variant, with the page-table gather FUSED into the
attention walk.

The XLA read (`ops/attention._live_page_attention`) walks the LIVE pages of
all slots in fixed blocks, in one loop a layer: a turn gathers a block of K
pages and a block of V pages from the pool, reads each back once (q.K,
probs.V) and folds the block into a running softmax kept by slot. The chip
runs one fusion at a time, so a turn's gather and its arithmetic never
overlap. This kernel gathers nothing: it walks the same live entries (ONE
kernel invocation a layer, one loop under the count of live entries the
caller computes on the device — no step exists for what is not live), copies
each entry's live pages from the pool in HBM into VMEM with its own DMAs, and
starts the next entry's copies before this entry's products, so every live
page crosses HBM once, under the arithmetic of the entry before it.
On a v5e it takes about half the XLA read's device time a layer at every
bf16 or int8 shape it reads in place — 385 -> 173 us at pythia-1.4b's
saturated cell's shape, of which the kernel itself 164 for bytes that take
139 at the chip's 819 GB/s — and loses for an fp8 pool, which that chip widens
in software (`ops.attention.slot_attention_impl` holds the table and keeps
such pools on the XLA read; PERF.md §6, PR 37).

Page-walk contract (mirrors the engine's host-side conventions, paging.py):

  - What is live is `ops.attention.live_entry_counts`, the rule the XLA read
    lists its entries by: a slot's queries attend up to `top = max_j
    positions[i, j]`, so its live pages are the table entries at or below
    `top // page_size` — an idle slot (position 0) is one page. An entry is a
    run of `ops.attention.kernel_run_pages` consecutive pages of one slot (1
    MiB of K: 16 pages at pythia-1.4b's widths, 8 at olmo-hybrid's); of a
    slot's last run only the live pages are copied. Table entries past a
    slot's live pages (the scratch page, page 0) are never read.
  - Masking is positional, not structural: query j of row i attends exactly
    ``cols <= positions[i, j]``, the same per-query mask the XLA oracle
    builds — rows of a run past a slot's last live page keep stale (finite)
    buffer contents and contribute exact zeros, so prefix-shared pages,
    ragged lengths, and freed slots all come out token-identical to the
    gather path.
  - Rows whose every lane is masked normalize against a tiny floor
    (`finalize_softmax`), never NaN — inactive slots ride the same dispatch.

Decode and verify are ONE kernel (decode is the ``s == 1`` block). An entry's
arithmetic is two matrix products over all its KV heads at once — the run as
the flat matrix ``[tokens * Hkv, D]``, the slot's queries as ``[s * Hq, D]``,
the columns of other KV heads masked (`_paged_kernel`) — in the operands'
dtype with fp32 accumulation, the shared online-softmax accumulator
(`ops/flash_common.py`) kept in VMEM across a slot's entries. GQA needs no
repeat of the pool: a query head's row keeps its KV head's columns. The pool
keeps its layout ``[N, page_size, Hkv, D]``, and where a page's trailing
``[Hkv, D]`` is whole tiles — ``D`` whole 128-lane rows, the heads whole packed
sublanes (`ops.attention.kernel_stages_pool`) — the kernel copies pages out of
it IN PLACE. The chip's compiler will not cut a page out of any other pool
(llama-1b's heads of 64 are stored padded to 128 lanes, and Mosaic refuses the
slice): such a pool is STAGED, padded with zero lanes and zero heads into one
the kernel can copy from, which is a copy of the whole pool a call. A named
`"pallas_paged"` therefore serves every shape; the engine's own choice takes
the kernel only where nothing is staged (`ops.attention.slot_attention_impl`).
The kernel is one invocation a layer, so every slot's page table rides SMEM
and every slot's queries sit in VMEM: what that bounds is
`ops.attention.kernel_refuses` (`tests/test_tpu_compile.py` compiles the
kernel for a described v5e at the serving cells' shapes, at llama-1b's, at a
tensor-parallel shard's and on both sides of those bounds).

QUANTIZED pools (``k_scale``/``v_scale`` operands, `ops/quantization.py`):
int8/fp8 pages are copied at 1 byte/value and widened in VMEM; their
per-page-per-head scales, gathered by the caller into one ``[1, tokens *
Hkv]`` row a (slot, run), are copied beside the pages and applied to the
scores (K) and to the probabilities (V) — the cache crosses HBM quantized.
Token-identical to the XLA dequantize-on-read oracle
(`tests/test_quantization.py`).

LATENT pools (`v_pool=None`; `ops.attention.slot_cache_attention` with
`v=None`, MLA's absorbed form): ONE pool `[num_pages, page_size, row]` with no
head axis, keys and values both. The same walk with one pool and one pair of
run buffers: a page `[page_size, row]` is copied out of the pool in place
(whole tiles or not at all, `ops.attention.kernel_refuses_rows`: latent rows
are never staged), the run in VMEM is already the flat matrix `[tokens, row]`
and every query head reads every column, so `_flat_rows` and the head mask
have nothing to do; scores are `q . run^T` at the family's own `scale` and the
output `probs . run[:, :value_dim]` from the SAME copy, so each live row
crosses HBM once a layer and the rope columns never reach the output. With 16
query rows both products are bound by loading the run into the matrix unit —
twice for one copy — so there the products, not the copies, set the pace, and
a page is a quarter of a K/V page's bytes, so the scalar core's turn a page
costs what the page's copy does. Hence the two things the latent arm does of
its own (`_paged_kernel`: `latent_products`, `copies`): an entry's products
run over the first pieces of its run that hold a live position
(`ops.attention.kernel_piece_pages`: 256 tokens), as one pair of products at
one of a few static widths; and a piece's pages are started back to back and
waited for once.
One layer on a v5e at kimi-vl-a3b's cell (128 slots x 128 pages of 16 rows of
640 bf16, 16 heads; device us, XLA read -> kernel, of which the kernel itself):
670 -> 251 (233) at 4,700 live pages, whose bytes take 117 at 819 GB/s; 784 ->
284 (266) at 6,000; 294 -> 133 (115) with every slot idle; 1,732 -> 611 (593)
with every page live; a verify block of 5, 1,259 -> 398 (312). The same walk
with the products looped a piece at a time and every page waited for alone
took 491 for the kernel at 4,700 and LOST to the XLA read in the cell (PERF.md
§6, PR 39: what each of the two cost).

Interpret mode (`interpret=None` auto-enables off-TPU) runs the same kernel
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
    finalize_softmax,
    init_softmax_state,
    online_softmax_update,
)


def _flat_rows(block, q):
    """A run's [tokens, Hkv, D] block as the [tokens * Hkv, D] matrix the two
    products take, in the queries' dtype. Merging the two leading axes moves nothing
    where a token's heads fill whole sublane tiles (16 rows of bf16, 8 of
    fp32); a pool that is stored narrower than it is computed in (int8/fp8),
    or whose heads fill half a tile (8 KV heads of bf16), goes through fp32,
    where they do."""
    t, h, d = block.shape
    if block.dtype != q.dtype or h % (32 // block.dtype.itemsize):
        block = block.astype(jnp.float32)
    return block.reshape(t * h, d).astype(q.dtype)


def _paged_kernel(
    n_ref, top_ref, tbl_ref, pos_ref,  # scalar prefetch (SMEM)
    q_ref, cols_ref, rows_ref, k_hbm, *rest,
    scale, page_size, run, pages_per_slot, block, quantized, piece,
):
    """The whole page walk of one layer: ONE loop over the live entries of
    all slots, slot-major, under the count the caller computed on the device
    (`n_ref`) — there are no steps for what is not live.

    An entry is a run of up to `run` consecutive pages of one slot; slot `b`
    has `top_ref[b] // (run * page_size) + 1` of them (`top_ref[b]` the last
    position its queries attend: `ops.attention.live_entry_counts`, which the
    XLA read lists its entries from too), and the loop carries (slot, run of
    the slot) from entry to entry on the scalar core. The pools stay in HBM;
    an entry's LIVE pages are copied, a page a DMA, into one of two VMEM
    buffers a pool, and the next entry's copies are started before this
    entry's products, so the chip's copy engine and its matrix unit work on
    neighbouring entries at once and every live page crosses HBM once. Pages
    of a run past the slot's last live one are not copied: their rows keep
    what an earlier entry left there (zeros at first), which is finite, and
    the positional mask gives them probability zero.

    An entry's arithmetic is two matrix products over ALL its KV heads at
    once. The run is the matrix `[tokens * Hkv, D]` (`_flat_rows`), a row a
    (token, KV head); the slot's queries are `[s * Hq, D]`, a row a (query
    position, query head). `q . K^T` is then `[s * Hq, tokens * Hkv]`, of
    which a row keeps the columns of ITS KV head (`cols_ref[1] ==
    rows_ref[:, 1]`) at tokens its query position attends (`cols_ref[0] <=
    pos_ref[slot, rows_ref[:, 0]] - the run's first token`) and masks the
    rest; the masked columns' probabilities are exact zeros, so `probs . V`
    over the same flat matrix sums a row's own head alone. The matrix unit is
    fed whole 128-wide tiles whatever the group size, where a product a head
    would feed it one row. The running softmax of the slot lives in `acc` /
    `m_scr` / `l_scr` across its entries and is written out at its last one.

    Quantized pools: a run's per-(token, head) K and V scales arrive as
    `[1, tokens * Hkv]` rows (gathered by the caller for every (slot, run),
    copied beside the pages) and are applied to the scores and to the
    probabilities — the pages cross HBM at int8/fp8 width and are widened in
    VMEM.

    A LATENT pool (`piece` > 0, the tokens in a piece of its run): the same
    walk — entries, copies, prefetch, the running softmax and its finish —
    over ONE pool and one pair of buffers, with an entry's arithmetic its own
    (`latent_products`) and a piece's pages copied as a group (`copies`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    latent = bool(piece)  # ONE pool of rows: no V pool, no head axis
    if latent:
        o_ref, k_buf, sems, acc, m_scr, l_scr = rest
    elif quantized:
        v_hbm, ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sems, acc, m_scr, l_scr = rest
    else:
        v_hbm, o_ref, k_buf, v_buf, sems, acc, m_scr, l_scr = rest
    span, runs = run * page_size, -(-pages_per_slot // run)

    def copies(slot, r, buf, fn):
        """`fn` (`start` or `wait`) on the DMAs of slot `slot`'s run `r` into buffer `buf`."""
        live = jnp.minimum(top_ref[slot] // page_size + 1 - r * run, run)

        def page(j, _):
            rows = pl.ds(j * page_size, page_size)
            pid = tbl_ref[slot * pages_per_slot + r * run + j]
            fn(pltpu.make_async_copy(k_hbm.at[pid], k_buf.at[buf, rows], sems.at[0, buf]))
            if not latent:
                fn(pltpu.make_async_copy(v_hbm.at[pid], v_buf.at[buf, rows], sems.at[1, buf]))

        if latent:
            # A page of latent rows is a quarter of a K/V page's bytes, and the
            # scalar core's turn a page (the table entry, the descriptor, the
            # loop) costs what its copy does: a piece's pages are started back
            # to back, unrolled, and waited for ONCE — they signal one semaphore,
            # which counts bytes, so one wait for the piece's bytes stands for
            # its pages' (of the descriptor a wait reads only the size and the
            # semaphore). What is left of a run's live pages goes page by page.
            pages = piece // page_size

            def piece_pages(g, _):
                if fn is wait:
                    whole = k_buf.at[buf, pl.ds(pl.multiple_of(g * piece, piece), piece)]
                    wait(pltpu.make_async_copy(whole, whole, sems.at[0, buf]))
                else:
                    for j in range(pages):
                        page(g * pages + j, None)

            jax.lax.fori_loop(0, live // pages, piece_pages, None)
            jax.lax.fori_loop(live // pages * pages, live, page, None)
        else:
            jax.lax.fori_loop(0, live, page, None)
        if quantized:
            fn(pltpu.make_async_copy(ks_hbm.at[slot * runs + r], ks_buf.at[buf], sems.at[0, buf]))
            fn(pltpu.make_async_copy(vs_hbm.at[slot * runs + r], vs_buf.at[buf], sems.at[1, buf]))

    def start(dma):
        dma.start()

    def wait(dma):
        dma.wait()

    k_buf[...] = jnp.zeros_like(k_buf)
    if not latent:
        v_buf[...] = jnp.zeros_like(v_buf)
    copies(0, 0, 0, start)

    def attend_limit(slot):
        """[s * Hq, 1]: the last position a row's query attends, its query position's off SMEM."""
        limit = jnp.full((rows_ref.shape[0], 1), pos_ref[slot * block], jnp.int32)
        for j in range(1, block):
            limit = jnp.where(rows_ref[:, 0:1] == j, pos_ref[slot * block + j], limit)
        return limit

    def latent_products(slot, r, buf):
        """An entry of a LATENT pool: the run in VMEM is already the flat
        matrix `[tokens, row]` and every query head reads every column, so
        there is no merge of rows and no head mask. Keys and values are the
        SAME copy: scores over the whole row, then `probs . run[:, :values]`,
        the row's leading columns (whole lane tiles, `acc`'s width: a free
        slice in VMEM), so the rope columns never reach the output. The two
        products run over the run's LIVE pieces alone — its first `n` pieces
        of `piece` tokens, `n` from the slot's position — as ONE pair of
        products at one of the `span // piece` static widths: what an entry
        costs on a v5e is mostly the chain of its two products and the
        softmax between them (~0.5 us whatever their width; a loop over the
        pieces pays it a piece: 491 us a layer against 288, PERF.md §6, PR
        39), and a slot's last run costs what is live of it."""
        q = q_ref[slot]  # [s * Hq, row]
        limit = attend_limit(slot) - r * span  # within the run
        pieces = (jnp.minimum(top_ref[slot] - r * span, span - 1) + piece) // piece  # that hold a live position

        for n in range(1, span // piece + 1):
            @pl.when(pieces == n)
            def _(width=n * piece):
                live = k_buf[buf, :width, :]  # [width, row]
                s = jax.lax.dot_general(
                    q, live, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
                ) * scale  # [s * Hq, width]
                online_softmax_update(
                    s, live[:, :acc.shape[1]], acc, m_scr, l_scr, valid=cols_ref[0:1, :width] <= limit
                )

    def entry(e, walk):
        slot, r = walk
        buf = e % 2
        more = (r + 1) * span <= top_ref[slot]  # the slot has another live run
        nxt = (jnp.where(more, slot, slot + 1), jnp.where(more, r + 1, 0))

        @pl.when(e + 1 < n_ref[0])
        def _prefetch():
            copies(*nxt, 1 - buf, start)

        @pl.when(r == 0)
        def _init():
            init_softmax_state(acc, m_scr, l_scr)

        copies(slot, r, buf, wait)
        if latent:
            latent_products(slot, r, buf)
        else:
            q = q_ref[slot]  # [s * Hq, D]
            k = _flat_rows(k_buf[buf], q)
            v = _flat_rows(v_buf[buf], q)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # [s * Hq, tokens * Hkv]
            if quantized:
                s = s * ks_buf[buf]
            limit = attend_limit(slot)
            valid = (cols_ref[1:2, :] == rows_ref[:, 1:2]) & (cols_ref[0:1, :] <= limit - r * span)
            online_softmax_update(
                s, v, acc, m_scr, l_scr, valid=valid, v_scale=vs_buf[buf] if quantized else None
            )

        @pl.when(jnp.logical_not(more))
        def _finish():
            out, _ = finalize_softmax(acc, m_scr, l_scr)
            o_ref[slot] = out.astype(o_ref.dtype)

        return nxt

    jax.lax.fori_loop(0, n_ref[0], entry, (jnp.int32(0), jnp.int32(0)))


def _paged_call(
    q, k_pool, v_pool, page_table, positions, scale, interpret, run, piece,
    k_scale=None, v_scale=None, value_dim=None,
):
    """Shared wrapper: what is live, the layout of queries and masks, the
    `pallas_call` (`scale` a Python float). `k_scale`/`v_scale` ([num_pages, Hkv] f32 traced operands,
    never Python scalars — TPU117) switch the kernel into fused-dequant mode;
    `v_pool=None` says `k_pool` is a LATENT pool, `[num_pages, page_size,
    row]`, keys and values both (`value_dim` leading columns of a row the
    values), which the kernel reads in place or not at all.

    What is live is `ops.attention.live_entry_counts` at the kernel's own run
    (`run` pages, the caller's `kernel_run_pages`, and for a latent pool the
    `piece` of it in tokens: statics of the call, like every number its
    program depends on): the last position a slot attends and the entries
    that makes, whose sum bounds the kernel's loop. It rides SMEM as
    scalar-prefetch operands beside the flattened page table and positions.
    Queries go in as `[B, s * Hq, D]` (a reshape: row `j * Hq + h` is query
    position `j`, head `h`). The column maps of the flat run (`cols`: token of
    its run, KV head) and of the query rows (`row_maps`: query position, KV
    head) are constants of the shapes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .attention import (
        _KERNEL_VMEM_BYTES,
        kernel_refuses_rows,
        kernel_stages_pool,
        live_entry_counts,
    )

    b, s, hq, head_dim = q.shape
    latent = v_pool is None  # [num_pages, page_size, row]: no head axis
    n_pages_pool, page_size = k_pool.shape[:2]
    hkv = 1 if latent else k_pool.shape[2]
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
    refused = latent and kernel_refuses_rows(page_size, head_dim, k_pool.dtype.itemsize)
    if refused:
        raise ValueError(f"a latent pool: {refused}; use attention_impl=\"xla\"")
    group, pages_per_slot = hq // hkv, page_table.shape[-1]  # query heads a KV head
    if not latent and kernel_stages_pool(hkv, head_dim, k_pool.dtype.itemsize):
        # STAGED: zero lanes up to whole 128-lane rows (the queries' too, so the
        # products ignore them) and zero KV heads, which no query row reads, up
        # to whole packed sublanes. A copy of both pools, made for this call.
        lanes, heads = -head_dim % LANE, -hkv % (4 // k_pool.dtype.itemsize)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, lanes)))
        k_pool, v_pool = (jnp.pad(pool, ((0, 0), (0, 0), (0, heads), (0, lanes))) for pool in (k_pool, v_pool))
        if quantized:
            k_scale, v_scale = (jnp.pad(sc, ((0, 0), (0, heads))) for sc in (k_scale, v_scale))
    hkv, d = (1, k_pool.shape[-1]) if latent else k_pool.shape[2:]
    rows = s * hq
    runs = -(-pages_per_slot // run)  # entries a full slot makes
    span = run * page_size
    # What the kernel writes a row: `d` lanes, or a latent row's values in whole lane tiles.
    out_d = d if value_dim is None else min(d, -(-value_dim // LANE) * LANE)

    table = jnp.clip(jnp.asarray(page_table, jnp.int32), 0, n_pages_pool - 1)
    pos = jnp.asarray(positions, jnp.int32).reshape(b, s)
    top, count = live_entry_counts(pos, span)
    col, row = np.arange(span * hkv, dtype=np.int32), np.arange(rows, dtype=np.int32)
    cols = np.stack([col // hkv, col % hkv])  # [2, span * Hkv]: token of the run, KV head
    row_maps = np.stack([row // hq, row % hq // group], axis=1)  # [s * Hq, 2]: query position, KV head

    kernel = functools.partial(
        _paged_kernel, scale=scale, page_size=page_size, run=run,
        pages_per_slot=pages_per_slot, block=s, quantized=quantized, piece=piece,
    )
    vmem, hbm = pl.BlockSpec(memory_space=pltpu.VMEM), pl.BlockSpec(memory_space=pl.ANY)
    pools = [k_pool] if latent else [k_pool, v_pool]
    in_specs = [vmem, vmem, vmem] + [hbm] * len(pools)
    operands = [q.reshape(b, rows, d), jnp.asarray(cols), jnp.asarray(row_maps), *pools]
    scratch = [pltpu.VMEM((2, span) + pool.shape[2:], pool.dtype) for pool in pools]  # two run buffers a pool
    if quantized:
        # [B * runs, 1, span * Hkv]: every (slot, run)'s scales a (token, head)
        # column; a table row is padded to whole runs with the scratch page.
        padded = jnp.pad(table, ((0, 0), (0, runs * run - pages_per_slot)))

        def scale_rows(pool):
            picked = jnp.take(pool, padded.reshape(-1), axis=0, mode="clip").reshape(b * runs, run, 1, hkv)
            return jnp.broadcast_to(picked, (b * runs, run, page_size, hkv)).reshape(b * runs, 1, span * hkv)

        in_specs += [hbm, hbm]
        operands += [scale_rows(k_scale), scale_rows(v_scale)]
        scratch += [pltpu.VMEM((2, 1, span * hkv), jnp.float32)] * 2
    scratch += [
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.VMEM((rows, out_d), jnp.float32),
        pltpu.VMEM((rows, LANE), jnp.float32),
        pltpu.VMEM((rows, LANE), jnp.float32),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(1,), in_specs=in_specs, out_specs=vmem,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, rows, out_d), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_KERNEL_VMEM_BYTES),
        name="paged_attention",
    )(jnp.sum(count).reshape(1), top, table.reshape(-1), pos.reshape(-1), *operands)
    return out.reshape(b, s, hq, out_d)[..., :head_dim if value_dim is None else value_dim]


@functools.lru_cache(maxsize=None)
def _latent_call():
    """A LATENT pool's call, traced and lowered ONCE for all the layers of a
    program that share its shapes: the latent arm's body is several times the
    K/V arm's to trace (its products at every static width, a piece's copies
    unrolled), and a call a layer cost the kimi cell's set-up 7.8 s of 56
    (PERF.md §6, PR 39). The K/V arm is traced a call a layer, as its
    programs were."""
    return jax.jit(_paged_call, static_argnums=(5, 6, 7, 8), static_argnames=("value_dim",))


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def paged_decode_attention(
    q, k_pool, v_pool, page_table, positions, *, scale=None, interpret=None,
    k_scale=None, v_scale=None, value_dim=None,
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
        k_scale=k_scale, v_scale=v_scale, value_dim=value_dim,
    )


def paged_verify_attention(
    q, k_pool, v_pool, page_table, positions, *, scale=None, interpret=None,
    k_scale=None, v_scale=None, value_dim=None,
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
        value_dim: a LATENT pool's (`v_pool=None`, `k_pool` [num_pages,
            page_size, row], `q` [B, s, Hq, row] in the row's space): the
            leading columns of a row that are its values. `scale` is then the
            family's own, not 1/sqrt(row).

    Returns [B, s, Hq, D], or [B, s, Hq, value_dim].
    """
    if q.ndim != 4:
        raise ValueError(f"paged_verify_attention takes [B, s, Hq, D] queries, got {q.shape}")
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])

    from .attention import kernel_piece_pages, kernel_run_pages

    # A run's pages, of the pool as it is, not as it is staged; the tokens in a piece of a latent run.
    latent, page_size = v_pool is None, k_pool.shape[1]
    run = kernel_run_pages(page_table.shape[-1], page_size, 1 if latent else k_pool.shape[2], q.shape[-1],
                           q.dtype.itemsize, latent=latent)
    piece = min(run, kernel_piece_pages(page_size)) * page_size if latent else 0
    return (_latent_call() if latent else _paged_call)(
        q, k_pool, v_pool, page_table, positions, float(scale), _auto_interpret(interpret), run, piece,
        k_scale=k_scale, v_scale=v_scale, value_dim=value_dim,
    )
