"""Shared online-softmax accumulator helpers for the Pallas attention kernels.

Both attention kernel families — the training flash kernel
(`ops/flash_attention.py`) and the serving paged-decode/block-verify kernels
(`ops/paged_attention.py`) — stream K/V blocks through the same numerically
stable accumulator: running max `m`, running normalizer `l`, and an
unnormalized output accumulator `acc`, all fp32 regardless of input dtype.
The update lives here ONCE so the two kernel families can never drift apart
on the one piece of math their parity contract depends on.

Layout convention (Mosaic): the per-row `m`/`l` stats ride a broadcast
128-lane trailing axis (`LANE`) because the minimum TPU tile is (8, 128) on
the last two dims — a `[rows]`-shaped stat cannot be blocked per grid step.
Same workaround as jax's in-tree TPU flash kernel's l/m buffers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: Additive mask value: large enough to zero a softmax lane, small enough that
#: exp(NEG_INF - m) never produces inf/nan under fp32.
NEG_INF = -1e30

#: Broadcast trailing-lane width for per-row softmax stats (Mosaic min tile).
LANE = 128


def init_softmax_state(acc, m_scr, l_scr):
    """Reset the accumulator scratch at the start of a row's K/V walk
    (`acc` [rows, D] fp32, `m_scr`/`l_scr` [rows, LANE] fp32)."""
    acc[:] = jnp.zeros_like(acc)
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)


def online_softmax_update(s, v, acc, m_scr, l_scr, valid=None, v_scale=None):
    """Fold one K/V block into the running softmax state.

    Args:
        s: [rows, block_k] fp32 scores for this block, already scaled, and
            masked (masked lanes at `NEG_INF`) unless `valid` says which
            lanes are live.
        v: [block_k, D] value block; the probabilities are cast to its dtype
            for the product (bf16 operands, fp32 accumulation — a no-op for
            an fp32 block).
        acc / m_scr / l_scr: scratch refs as in `init_softmax_state`.
        valid: optional [rows, block_k] bool, the live lanes of an unmasked
            `s`: the others are masked here AND their probabilities set to
            exact zeros, so a row that has met no live lane yet (its maximum
            still `NEG_INF`) adds nothing. Needed where a block may hold lanes
            no row of it owns (the paged kernel's other heads' columns).
        v_scale: optional [1, block_k] fp32 dequantization scale of `v`'s
            rows, applied to the probabilities on their way into the product
            (the row sum stays unscaled).
    """
    if valid is not None:
        s = jnp.where(valid, s, NEG_INF)
    m_prev = m_scr[:, 0:1]  # [rows, 1] (lane dim is broadcast)
    l_prev = l_scr[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)  # [rows, block_k]
    if valid is not None:
        p = jnp.where(valid, p, 0.0)
    correction = jnp.exp(m_prev - m_new)  # [rows, 1]
    l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
    if v_scale is not None:
        p = p * v_scale
    acc[:] = acc[:] * correction + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)


def finalize_softmax(acc, m_scr, l_scr):
    """(normalized output [rows, D], logsumexp [rows, 1]) after the last block.

    Rows whose every lane was masked (l == 0) normalize against a tiny floor
    instead of dividing by zero — they come out ~0, never NaN, which is what
    lets inactive serving slots ride the same dispatch as live ones.
    """
    l = l_scr[:, 0:1]
    safe_l = jnp.maximum(l, 1e-30)
    lse = (m_scr[:, 0:1] + jnp.log(safe_l)).astype(jnp.float32)
    return acc[:] / safe_l, lse


# ---- The keys-first form (PR 43), for kernels whose query rows are many: the
# same state and the same update with a block held transposed. It sits below
# the row-major form so that the older kernels' source locations stay put.

#: Broadcast leading-sublane height for the stats of the keys-first form.
SUBLANE = 8


def online_softmax_update_keys_first(s, v_t, acc, m_scr, l_scr):
    """`online_softmax_update` for a block held TRANSPOSED, keys on sublanes
    and query rows on lanes: `s` [block_k, rows] fp32 (scaled and masked),
    `v_t` [D, block_k], `acc` [D, rows], `m_scr` / `l_scr` [SUBLANE, rows] (a
    row's stat repeated down the minimum tile's 8 sublanes). The same update,
    laid out so that the maximum and the sum over a block's keys are
    elementwise across vregs and one 8-sublane fold a block; the row-major
    form folds 128 lanes for every 8 rows of every block, which on a v5e held
    a [512, 256] block of a prefill to four times its products' time (PERF.md
    section 6, PR 43). For kernels whose rows are many and whose blocks are
    whole tiles both ways; a decode step's few rows stay row-major."""
    m_prev = m_scr[0:1, :]  # [1, rows]
    l_prev = l_scr[0:1, :]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    p = jnp.exp(s - m_new)  # [block_k, rows]
    correction = jnp.exp(m_prev - m_new)
    l_new = l_prev * correction + jnp.sum(p, axis=0, keepdims=True)
    acc[:] = acc[:] * correction + jax.lax.dot_general(
        v_t, p.astype(v_t.dtype), (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)


def finalize_softmax_keys_first(acc, l_scr):
    """The normalized output [D, rows] of the keys-first form; a row that met
    no live key comes out ~0, never NaN, as `finalize_softmax`'s does."""
    return acc[:] / jnp.maximum(l_scr[0:1, :], 1e-30)
