"""Pallas TPU flash attention for a prefill INTO a dense decode cache: a block
of `rows` new tokens, written at the cache's running index `cur`, attends the
cache's `window` positions under `ops.attention.update_decode_cache`'s mask
(`cols <= cur + row`) — and the kernel walks, for each block of query rows,
only the key blocks at or before that block's causal frontier `cur + last_row`.
No `[heads, rows, window]` array exists: the masked XLA call scores every
position of the window whatever the rows can see, which for a 1,024-row insert
of an empty 2,176-position slot is four times the products the mask keeps.

`cur` is a scalar operand (scalar prefetch), so one compiled program serves
every matched prefix. Forward only; keys and values may differ in head size
(MLA: 192 and 128). The part of a key that all heads share (MLA's rotary 64,
one row a token) rides as `shared_k` and is never broadcast to the heads.

One grid step is one (batch entry, head, block of query rows): the head's keys
and values of the WHOLE window are one VMEM block, copied once a head (the
copy for the next head runs under this head's products), and the step loops
over its visible key blocks in VMEM — first those every row sees whole (no
mask is built), then the ones the frontier crosses. The walk is a loop inside
the kernel, not a grid axis: a grid axis needs a block that divides the
window, the serving window of 2,176 = 17 x 128 leaves 128 alone, and a grid
step costs about what the products of one [512, 128] tile do.

A block's scores are held TRANSPOSED, `[block_k, block_q]` — keys on
sublanes, query rows on lanes — so the queries and the values come in
transposed (`[.., D, rows]`, `[.., D, window]`: layouts a model's own products
write for nothing) and the output tile is turned once a grid step. The
online-softmax state is `ops.flash_common`'s keys-first form, which says why.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .flash_common import (
    LANE,
    NEG_INF,
    SUBLANE,
    finalize_softmax_keys_first,
    init_softmax_state,
    online_softmax_update_keys_first,
)

#: Query rows a grid step, key rows a loop turn (timed on a v5e at the serving
#: cells' shapes: PERF.md §6, PR 43).
BLOCK_Q, BLOCK_K = 512, 512

#: What one head's keys, values and shared key rows of the whole window may
#: hold in VMEM (the pipeline keeps two copies); a longer window is refused.
_RESIDENT_BYTES = 8 * 2 ** 20
_VMEM_LIMIT_BYTES = 48 * 2 ** 20


def _blocks(rows: int, window: int) -> Tuple[int, int]:
    """(query rows a grid step — whole 128-lane tiles —, key rows a loop turn) at a call's shapes."""
    return min(BLOCK_Q, -(-rows // LANE) * LANE), min(BLOCK_K, window)


def frontier_refuses(window: int, key_dim: int, value_dim: int, shared_dim: int, itemsize: int) -> Optional[str]:
    """Why the kernel cannot take a call's static shapes, or None where it
    can: the window whole 128-row key blocks (a loop turn's slice starts on a
    tile), values whole 128-lane tiles (the output is written `[rows, heads *
    value_dim]`, a head a column block), and one head's keys and values of the
    whole window inside `_RESIDENT_BYTES`."""
    if window % LANE or value_dim % LANE:
        return f"a window of {window} positions and values of {value_dim} are not whole 128-wide tiles"
    lanes = sum(-(-d // LANE) * LANE for d in (key_dim, value_dim, shared_dim) if d)
    if window * lanes * itemsize > _RESIDENT_BYTES:
        return (f"one head's keys and values of {window} positions hold {window * lanes * itemsize} bytes, "
                f"and the kernel keeps at most {_RESIDENT_BYTES} in VMEM")
    return None


def frontier_serves(rows: int, window: int, key_dim: int, value_dim: int, shared_dim: int, itemsize: int) -> bool:
    """Whether a cached prefill's attention is this kernel, from what the call
    can observe: a TPU (elsewhere the masked XLA product, which the CPU tests
    and the float32 reference run), a block of rows and not a decode step's
    one, and shapes `frontier_refuses` passes."""
    return (jax.default_backend() == "tpu" and rows > 1
            and frontier_refuses(window, key_dim, value_dim, shared_dim, itemsize) is None)


def frontier_key_blocks(cur: int, rows: int, window: int) -> Tuple[int, int]:
    """(key blocks the kernel visits, key blocks a walk of the whole window
    would) for one head of one layer: host arithmetic over the kernel's own
    block sizes, for a span's count of how far the frontier cuts."""
    block_q, block_k = _blocks(rows, window)
    steps = -(-rows // block_q)
    visited = sum(min(cur + (i + 1) * block_q - 1, window - 1) // block_k + 1 for i in range(steps))
    return visited, steps * -(-window // block_k)


def _frontier_kernel(cur_ref, q_ref, k_ref, v_ref, *rest, scale, block_q, block_k, window, shared):
    from jax.experimental import pallas as pl

    shared_ref = rest[0] if shared else None
    o_ref, acc, m_scr, l_scr = rest[-4:]
    first_row = cur_ref[0] + pl.program_id(2) * block_q  # the block's first row, as a cache position
    key_dim = k_ref.shape[-1]
    q_t = q_ref[0, 0]  # [Dk + Ds, block_q]
    init_softmax_state(acc, m_scr, l_scr)

    def scores(start):  # [block_k, block_q]
        s = jnp.dot(k_ref[0, 0, pl.ds(start, block_k), :], q_t[:key_dim], preferred_element_type=jnp.float32)
        if shared:
            s += jnp.dot(shared_ref[0, pl.ds(start, block_k), :], q_t[key_dim:], preferred_element_type=jnp.float32)
        return s * scale

    def below(j, carry):  # every row of the block sees every key of block j
        start = pl.multiple_of(j * block_k, block_k)
        online_softmax_update_keys_first(scores(start), v_ref[0, 0, :, pl.ds(start, block_k)], acc, m_scr, l_scr)
        return carry

    def crossing(j, carry):  # the frontier runs through block j: `update_decode_cache`'s mask
        # The last block of a window that `block_k` does not divide is read
        # from where it still fits, and the keys it repeats are masked.
        start = pl.multiple_of(jnp.minimum(j * block_k, window - block_k), LANE)
        cols = start + jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
        rows = first_row + jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1)
        s = jnp.where((cols <= rows) & (cols >= j * block_k), scores(start), NEG_INF)
        online_softmax_update_keys_first(s, v_ref[0, 0, :, pl.ds(start, block_k)], acc, m_scr, l_scr)
        return carry

    whole = jnp.minimum((first_row + 1) // block_k, window // block_k)
    visited = jnp.minimum(first_row + block_q - 1, window - 1) // block_k + 1
    jax.lax.fori_loop(0, whole, below, 0)
    jax.lax.fori_loop(whole, visited, crossing, 0)
    o_ref[0] = finalize_softmax_keys_first(acc, l_scr).T.astype(o_ref.dtype)


def _frontier_pallas(q_t, k, v_t, shared_k, cur, scale, block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, heads, q_dim, rows = q_t.shape
    window, key_dim, value_dim = k.shape[2], k.shape[3], v_t.shape[2]
    pad = -rows % block_q  # 0 at an engine's buckets of 128 rows and more
    q_t = jnp.pad(q_t, ((0, 0), (0, 0), (0, 0), (0, pad)))
    in_specs = [
        pl.BlockSpec((1, 1, q_dim, block_q), lambda n, h, i, cur: (n, h, 0, i)),
        pl.BlockSpec((1, 1, window, key_dim), lambda n, h, i, cur: (n, h, 0, 0)),
        pl.BlockSpec((1, 1, value_dim, window), lambda n, h, i, cur: (n, h, 0, 0)),
    ]
    operands = [q_t, k, v_t]
    if shared_k is not None:
        in_specs.append(pl.BlockSpec((1, window, shared_k.shape[-1]), lambda n, h, i, cur: (n, 0, 0)))
        operands.append(shared_k)
    out = pl.pallas_call(
        functools.partial(_frontier_kernel, scale=scale, block_q=block_q, block_k=block_k, window=window,
                          shared=shared_k is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, heads, (rows + pad) // block_q),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, block_q, value_dim), lambda n, h, i, cur: (n, i, h)),
            scratch_shapes=[
                pltpu.VMEM((value_dim, block_q), jnp.float32),
                pltpu.VMEM((SUBLANE, block_q), jnp.float32),
                pltpu.VMEM((SUBLANE, block_q), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, rows + pad, heads * value_dim), q_t.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="frontier_attention",
    )(jnp.asarray(cur, jnp.int32).reshape(1), *operands)
    return out[:, :rows]


@functools.lru_cache(maxsize=None)
def _frontier_call():
    """The kernel's call, traced and lowered ONCE for all the layers of a
    program that share its shapes, as `paged_attention._latent_call` and
    `hyper_connection._kernel_calls` are: a kernel traced a layer is what a
    cell's `setup_s` pays in every one of its insert programs. The block sizes
    are statics of the call, so a test that shrinks them is never served a
    stale trace."""
    return jax.jit(_frontier_pallas, static_argnums=(5, 6, 7, 8))


def frontier_attention(q_t, k, v_t, cur, *, scale: float, shared_k=None, interpret: Optional[bool] = None):
    """Attention of `rows` new tokens over a dense cache that already holds
    them at positions `cur .. cur + rows - 1`: row i attends `cols <= cur + i`.

    Args:
        q_t: [B, H, Dk, rows] queries, head-major and TRANSPOSED (or
            [B, H, Dk + Ds, rows] with `shared_k`).
        k: [B, H, window, Dk] keys of every cache position, head-major.
        v_t: [B, H, Dv, window] values, transposed; `Dv` need not be `Dk`.
        cur: int32 scalar (traced), the cache's index before the write;
            `cur + rows <= window`.
        scale: the scores' multiplier.
        shared_k: optional [B, window, Ds], a trailing part of every head's
            key that the heads share; `q_t`'s trailing `Ds` rows meet it.
        interpret: None = the Pallas interpreter off a TPU, compiled on one.

    Returns [B, rows, H * Dv] in `q_t`'s type: bfloat16 (or float32) operands
    into the matrix unit, float32 accumulation, a float32 softmax. A shape
    `frontier_refuses` names is a `ValueError`.
    """
    shared_dim = 0 if shared_k is None else shared_k.shape[-1]
    if q_t.shape[2] != k.shape[-1] + shared_dim:
        raise ValueError(f"queries of {q_t.shape[2]} do not meet keys of {k.shape[-1]} + {shared_dim} shared")
    refused = frontier_refuses(k.shape[2], k.shape[3], v_t.shape[2], shared_dim, q_t.dtype.itemsize)
    if refused:
        raise ValueError(f"frontier_attention: {refused}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _frontier_call()(q_t, k, v_t, shared_k, cur, float(scale), *_blocks(q_t.shape[3], k.shape[2]), bool(interpret))
