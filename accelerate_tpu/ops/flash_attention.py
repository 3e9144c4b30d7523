"""Pallas TPU flash attention (forward + backward), the hot-op kernel behind
`ops.attention.dot_product_attention`.

FlashAttention-2 style: online-softmax over KV blocks in the forward (O(S) memory, no
[S,S] materialization), saved logsumexp + recompute in the backward. Layout inside the
kernels is [B*H, S, D] with a 3-D grid; the innermost grid axis streams KV (forward,
dq) or Q (dk/dv) blocks through VMEM scratch accumulators, so HBM traffic per block is
one read of each operand tile — the MXU sees back-to-back (Bq×D)@(D×Bk) matmuls.

Interpret mode (`interpret=True`) runs the same kernels on CPU for tests; real runs
compile for TPU. All accumulation is fp32 regardless of input dtype.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

# The online-softmax accumulator math is shared with the serving paged
# kernels (ops/paged_attention.py) — one implementation, one parity contract.
from .flash_common import (
    LANE,
    NEG_INF,
    finalize_softmax,
    init_softmax_state,
    online_softmax_update,
)


def _causal_block_visible(iq, ik, block_q: int, block_k: int, offset: int) -> "jnp.ndarray":
    """Whether KV block ik has any unmasked position for Q block iq.

    `offset = Skv - Sq` gives bottom-right alignment (query i attends keys
    j <= i + offset), matching `ops.attention.make_causal_mask`."""
    q_last = (iq + 1) * block_q - 1
    k_first = ik * block_k
    return k_first <= q_last + offset


def _block_mask(iq, ik, block_q: int, block_k: int, offset: int):
    """[Bq, Bk] bottom-right-aligned causal mask for the (iq, ik) tile (True = attend)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + iq * block_q
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + ik * block_k
    return cols <= rows + offset


# ---------------------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr, *, scale, causal, block_q, block_k, offset):
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    ik = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        init_softmax_state(acc, m_scr, l_scr)

    run = _causal_block_visible(iq, ik, block_q, block_k, offset) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)  # [Bq, D]
        k = k_ref[0].astype(jnp.float32)  # [Bk, D]
        v = v_ref[0].astype(jnp.float32)  # [Bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [Bq, Bk]
        if causal:
            s = jnp.where(_block_mask(iq, ik, block_q, block_k, offset), s, NEG_INF)
        online_softmax_update(s, v, acc, m_scr, l_scr)

    @pl.when(ik == n_k - 1)
    def _finish():
        out, lse = finalize_softmax(acc, m_scr, l_scr)
        o_ref[0] = out.astype(o_ref.dtype)
        # lse carries a broadcast 128-lane trailing dim: Mosaic requires the last
        # two block dims to be (8k, 128k) or match the array, so a [BH, S] layout
        # cannot be blocked (1, block_q). Same workaround as jax's in-tree TPU
        # flash kernel (l/m stored [B, H, S, MIN_BLOCK_SIZE]).
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref[0].shape)


def _fwd_call(q, k, v, scale, causal, block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = q.shape
    Sk = k.shape[1]
    grid = (BH, S // block_q, Sk // block_k)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k, offset=Sk - S
    )
    o, lse = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, LANE), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, LANE), lambda b, i, j: (b, i, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return o, lse


# --------------------------------------------------------------------------- backward
def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, block_q, block_k, offset):
    from jax.experimental import pallas as pl

    ik = pl.program_id(1)
    iq = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = _causal_block_visible(iq, ik, block_q, block_k, offset) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)  # [Bq, D]
        k = k_ref[0].astype(jnp.float32)  # [Bk, D]
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)  # [Bq, D]
        lse = lse_ref[0][:, 0:1]  # [Bq, 1] (lane dim is broadcast)
        delta = delta_ref[0][:, 0:1]  # [Bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            s = jnp.where(_block_mask(iq, ik, block_q, block_k, offset), s, NEG_INF)
        p = jnp.exp(s - lse)  # [Bq, Bk]
        # dv += p^T @ do
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        # dp = do @ v^T ; ds = p * (dp - delta) * scale
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale  # [Bq, Bk]
        # dk += ds^T @ q
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(iq == n_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc, *, scale, causal, block_q, block_k, offset):
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    ik = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = _causal_block_visible(iq, ik, block_q, block_k, offset) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, 0:1]  # [Bq, 1] (lane dim is broadcast)
        delta = delta_ref[0][:, 0:1]  # [Bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            s = jnp.where(_block_mask(iq, ik, block_q, block_k, offset), s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale  # [Bq, Bk]
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ik == n_k - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_call(q, k, v, o, lse, do, scale, causal, block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = q.shape
    Sk = k.shape[1]
    # [BH, S, LANE] — broadcast lane dim for the same Mosaic tiling reason as lse.
    delta = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[..., None], (BH, S, LANE)
    )

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k, offset=Sk - S
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((BH, Sk, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), q.dtype),
        ),
        grid=(BH, Sk // block_k, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),  # q
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),  # k
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),  # v
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),  # do
            pl.BlockSpec((1, block_q, LANE), lambda b, j, i: (b, i, 0)),  # lse
            pl.BlockSpec((1, block_q, LANE), lambda b, j, i: (b, i, 0)),  # delta
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(q, k, v, do, lse, delta)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k, offset=Sk - S
    )
    dq = pl.pallas_call(
        dq_kernel,
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        grid=(BH, S // block_q, Sk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, LANE), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, LANE), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------------------ public API
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhsd(q, k, v, scale, causal, block_q, block_k, interpret):
    o, _ = _fwd_call(q, k, v, scale, causal, block_q, block_k, interpret)
    return o


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    o, lse = _fwd_call(q, k, v, scale, causal, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _bwd_call(q, k, v, o, lse, do, scale, causal, block_q, block_k, interpret)
    return dq, dk, dv


_flash_bhsd.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
):
    """Flash attention on [B, S, H, D] (BSHD) inputs; supports GQA by KV-head repeat.

    Requires Sq % block_q == 0 and Skv % block_k == 0 (callers pad or fall back to the
    XLA path via `dot_product_attention`). `interpret=None` auto-enables the Pallas
    interpreter off-TPU (CPU tests) and compiles on TPU.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    if sq % block_q or skv % block_k:
        raise ValueError(f"Sequence lengths ({sq}, {skv}) must divide blocks ({block_q}, {block_k})")
    if causal and sq > skv:
        # Bottom-right alignment would leave the first (sq - skv) query rows with no
        # visible keys — a degenerate mask the XLA path also can't represent sensibly.
        raise ValueError(f"causal flash attention requires Sq <= Skv, got ({sq}, {skv})")
    if hq != hkv:
        if hq % hkv:
            raise ValueError(f"GQA requires Hq % Hkv == 0, got {hq}, {hkv}")
        reps = hq // hkv
        k = jnp.repeat(k, reps, axis=2)
        v = jnp.repeat(v, reps, axis=2)
    # BSHD -> [B*H, S, D]
    qt = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * hq, skv, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hq, skv, d)
    o = _flash_bhsd(qt, kt, vt, float(scale), bool(causal), block_q, block_k, interpret)
    return o.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)
