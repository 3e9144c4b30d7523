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
