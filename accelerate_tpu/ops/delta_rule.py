"""The gated delta rule (Yang et al., arXiv:2412.06464) and the short causal
convolution in front of it: what a linear-attention layer computes where a
softmax layer reads a cache of keys and values.

A head keeps a matrix ``S`` of ``[key_dim, value_dim]`` float32 instead of a
growing cache. Token ``t`` brings a query and a key (unit vectors), a value, a
decay ``alpha_t`` in (0, 1) and a write strength ``beta_t`` (in (0, 2) when the
model allows negative eigenvalues):

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - (alpha_t S_{t-1})^T k_t)^T
    o_t = S_t^T q_t

Two forms of the same recurrence:

  - `gated_delta_chunked` (prefill): chunks of 64 tokens in the WY / UT form of
    the paper — inside a chunk the rank-one updates are folded into one
    triangular solve and a few matrix products, and only the chunk boundary
    carries ``S`` — so a prompt costs matmuls, not a scan over tokens. A
    position with ``beta = 0`` and ``log_alpha = 0`` leaves ``S`` as it found
    it, which is how a bucket's padding is masked.
  - `gated_delta_step` (decode): one token a row. The step reads ``S`` once and
    writes it once; on a TPU it is the Pallas kernel `delta_step`, elsewhere
    the same arithmetic in `jax.numpy`.

How ``S`` is stored (`to_slot_layout`): ``[rows, key_dim, heads * value_dim]``
float32. The TPU lays an array's two minor axes out in tiles of 8 x 128; a
``[.., 96, 192]`` float32 matrix a head is stored as ``[.., 96, 256]`` (a third
more bytes, held and moved every step: the compile for a described v5e says
143.1 MB where the values are 106.2), and 30 heads are not whole sublane
tiles either. With the heads' value columns side by side the minor axis is
5,760 = 45 tiles and the next one 96 = 12: the array is stored at its bytes.
The step then needs a key broadcast over its head's 192 columns, which XLA
materialises at the size of ``S``; the kernel makes it in fast memory with one
small matmul against a 0/1 matrix (exact: one bfloat16 term a column).

All accumulation is float32; q, k and v come in the model's compute type.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

#: Tokens a chunk of the prefill form.
CHUNK = 64

_HIGHEST = jax.lax.Precision.HIGHEST


def to_slot_layout(state):
    """``[rows, heads, key_dim, value_dim]`` -> ``[rows, key_dim, heads * value_dim]``."""
    rows, heads, dk, dv = state.shape
    return state.transpose(0, 2, 1, 3).reshape(rows, dk, heads * dv)


def from_slot_layout(state, heads: int):
    """The inverse of `to_slot_layout`."""
    rows, dk, width = state.shape
    return state.reshape(rows, dk, heads, width // heads).transpose(0, 2, 1, 3)


def l2_normalize(x, eps: float = 1e-6):
    """``x / ||x||`` over the last axis, in float32, returned in x's type."""
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + eps)).astype(x.dtype)


# ------------------------------------------------------------------ convolution
def causal_conv(x, kernel, conv_state, valid=None):
    """Depthwise causal convolution over time, no bias: ``y_t = sum_j
    kernel[j] * x_{t - (W-1) + j}``, the inputs before the block taken from
    ``conv_state`` (the last ``W - 1`` inputs, oldest first).

    x [B, T, C], kernel [W, C], conv_state [B, W-1, C]. ``valid`` [B, T] marks
    the real positions of a padded block: a padded input counts as zero, and
    the state returned is the last ``W - 1`` inputs up to the last REAL one —
    what a later token has to see — not the bucket's tail. Returns (y in x's
    type, the new conv_state in conv_state's type)."""
    b, t, c = x.shape
    taps = kernel.shape[0]
    if valid is not None:
        x = jnp.where(valid[..., None], x, jnp.zeros((), x.dtype))
    padded = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)  # [B, W-1+T, C]
    y = sum(
        padded[:, j:j + t].astype(jnp.float32) * kernel[j].astype(jnp.float32)
        for j in range(taps)
    )
    if valid is None:
        new_state = padded[:, t:]
    else:
        # one past the last real position: the real length of a right-padded
        # bucket, the block's length of a left-padded prompt
        end = jnp.max(jnp.where(valid, jnp.arange(1, t + 1, dtype=jnp.int32), 0), axis=1)
        new_state = jax.vmap(
            lambda row, at: jax.lax.dynamic_slice_in_dim(row, at, taps - 1, axis=0)
        )(padded, end)
    return y.astype(x.dtype), new_state.astype(conv_state.dtype)


# ------------------------------------------------------------- the prefill form
def gated_delta_chunked(q, k, v, log_alpha, beta, state, *, chunk: int = CHUNK,
                        precision=_HIGHEST):
    """The recurrence over a block of tokens, chunk by chunk.

    q, k [B, T, H, dk] (already normalised and scaled), v [B, T, H, dv],
    log_alpha, beta [B, T, H] float32, state [B, H, dk, dv] float32 (the state
    before the block). Returns (o [B, T, H, dv] float32, the state after the
    block). T need not be a multiple of ``chunk``: the tail is padded with
    positions that leave the state alone.

    Inside a chunk, with ``g`` the running sum of ``log_alpha`` and ``L`` the
    strictly lower triangle of ``beta_i (k_i . k_j) exp(g_i - g_j)``: ``(I + L)
    [U | W] = [beta v | beta exp(g) k]``; then against the incoming state
    ``v' = U - W S``, ``o = (q exp(g)) S + tril(q k^T exp(g_i - g_j)) v'`` and
    ``S <- exp(g_C) S + (k exp(g_C - g))^T v'``."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % chunk
    if pad:
        widths = ((0, 0), (0, pad), (0, 0))
        q, k, v = (jnp.pad(x, widths + ((0, 0),)) for x in (q, k, v))
        log_alpha, beta = jnp.pad(log_alpha, widths), jnp.pad(beta, widths)
    n = (t + pad) // chunk

    def chunks(x):  # [B, T, H, ...] -> [N, B, H, C, ...]
        x = x.astype(jnp.float32).reshape((b, n, chunk, h) + x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    q, k, v, log_alpha, beta = (chunks(x) for x in (q, k, v, log_alpha, beta))
    g = jnp.cumsum(log_alpha, axis=-1)  # [N, B, H, C]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # exp only where i >= j: above the diagonal the difference is positive and unbounded
    decay = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :], -jnp.inf))
    kk = jnp.einsum("...ik,...jk->...ij", k, k, precision=precision)
    strict = jnp.tril(beta[..., :, None] * kk * decay, -1)
    rhs = jnp.concatenate([v * beta[..., None], k * (beta * jnp.exp(g))[..., None]], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        strict + jnp.eye(chunk, dtype=jnp.float32), rhs, lower=True, unit_diagonal=True)
    u, w = solved[..., :dv], solved[..., dv:]
    qk = jnp.einsum("...ik,...jk->...ij", q, k, precision=precision) * decay  # zero above the diagonal
    q_in = q * jnp.exp(g)[..., None]
    k_out = k * jnp.exp(g[..., -1:] - g)[..., None]
    carry_decay = jnp.exp(g[..., -1])[..., None, None]  # [N, B, H, 1, 1]

    def one_chunk(s, xs):
        u_n, w_n, qk_n, q_n, k_n, decay_n = xs
        v_new = u_n - jnp.einsum("bhck,bhkv->bhcv", w_n, s, precision=precision)
        o_n = (jnp.einsum("bhck,bhkv->bhcv", q_n, s, precision=precision)
               + jnp.einsum("bhij,bhjv->bhiv", qk_n, v_new, precision=precision))
        s = decay_n * s + jnp.einsum("bhck,bhcv->bhkv", k_n, v_new, precision=precision)
        return s, o_n

    state, o = jax.lax.scan(one_chunk, state.astype(jnp.float32), (u, w, qk, q_in, k_out, carry_decay))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)  # [N, B, H, C, dv] -> [B, N, C, H, dv]
    return o.reshape(b, n * chunk, h, dv)[:, :t], state


# -------------------------------------------------------------- the decode form
def _expand(x, dv: int):
    """A number a head -> that head's ``dv`` value columns: [..., H] -> [..., H * dv]."""
    return jnp.repeat(x, dv, axis=-1)


def _delta_step_xla(state, q, k, v, alpha, beta):
    """`gated_delta_step` in `jax.numpy`, on the slot layout."""
    b, h, dk = q.shape
    dv = v.shape[-1]
    q32, k32 = q.astype(jnp.float32), k.astype(jnp.float32)
    k_cols = _expand(jnp.swapaxes(k32, 1, 2), dv)  # [B, dk, H * dv]
    q_cols = _expand(jnp.swapaxes(q32, 1, 2), dv)
    a, bt = _expand(alpha, dv), _expand(beta, dv)
    k_s = jnp.sum(k_cols * state, axis=1)
    q_s = jnp.sum(q_cols * state, axis=1)
    u = bt * (v.astype(jnp.float32).reshape(b, h * dv) - a * k_s)
    state = a[:, None, :] * state + k_cols * u[:, None, :]
    o = a * q_s + _expand(jnp.sum(q32 * k32, axis=-1), dv) * u
    return o.reshape(b, h, dv), state


def _delta_step_kernel(s_ref, kt_ref, qt_ref, e_ref, a_ref, b_ref, v_ref, qk_ref, s_out, o_out):
    """One row (a slot), one block of value columns: S in, S out, once each."""
    s = s_ref[0]  # [dk, cols] float32
    e = e_ref[...]  # [H, cols] 0/1: column c belongs to head c // dv
    # a head's key over its own columns, made in fast memory: one exact term a column
    k_cols = jnp.dot(kt_ref[0], e, preferred_element_type=jnp.float32)
    q_cols = jnp.dot(qt_ref[0], e, preferred_element_type=jnp.float32)
    a = a_ref[0]  # [1, cols]
    k_s = jnp.sum(k_cols * s, axis=0, keepdims=True)
    q_s = jnp.sum(q_cols * s, axis=0, keepdims=True)
    u = b_ref[0] * (v_ref[0] - a * k_s)
    s_out[0] = a * s + k_cols * u
    o_out[0] = a * q_s + qk_ref[0] * u


def _delta_step_pallas(state, q, k, v, alpha, beta, interpret: bool = False,
                       column_block: Optional[int] = None):
    """`gated_delta_step` as the kernel `delta_step`: grid (rows, column
    blocks), a step streaming one row's ``[dk, column_block]`` of S through
    fast memory. ``column_block`` (whole heads, whole 128-lane tiles) defaults
    to every column: 2.2 MB a block at 96 x 5,760."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, dk = q.shape
    dv = v.shape[-1]
    width = h * dv
    cols = width if column_block is None else int(column_block)
    # per-head numbers over their columns: a few [B, H * dv] float32 rows, which XLA makes for nothing
    a = _expand(alpha, dv)[:, None, :]
    bt = _expand(beta, dv)[:, None, :]
    qk = _expand(jnp.sum(q.astype(jnp.float32) * k.astype(jnp.float32), axis=-1), dv)[:, None, :]
    v_row = v.astype(jnp.float32).reshape(b, 1, width)
    kt = jnp.swapaxes(k, 1, 2).astype(jnp.bfloat16)  # [B, dk, H]
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.bfloat16)
    expand = (jnp.arange(width)[None, :] // dv == jnp.arange(h)[:, None]).astype(jnp.bfloat16)

    row = lambda shape: pl.BlockSpec((1,) + shape, lambda i, j: (i, 0, j))  # noqa: E731
    heads_spec = pl.BlockSpec((1, dk, h), lambda i, j: (i, 0, 0))
    new_state, o = pl.pallas_call(
        _delta_step_kernel,
        grid=(b, width // cols),
        in_specs=[row((dk, cols)), heads_spec, heads_spec,
                  pl.BlockSpec((h, cols), lambda i, j: (0, j)),
                  row((1, cols)), row((1, cols)), row((1, cols)), row((1, cols))],
        out_specs=[row((dk, cols)), row((1, cols))],
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, width), jnp.float32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="delta_step",
    )(state, kt, qt, expand, a, bt, v_row, qk)
    return o.reshape(b, h, dv), new_state


def gated_delta_step(q, k, v, alpha, beta, state, *, impl: Optional[str] = None):
    """One token a row. q, k [B, H, dk] (normalised and scaled), v [B, H, dv],
    alpha, beta [B, H] float32, state [B, dk, H * dv] float32 (`to_slot_layout`).
    Returns (o [B, H, dv] float32, the new state).

    ``impl``: "pallas" (the kernel `delta_step`; compiled on a TPU, the
    interpreter elsewhere), "xla" (`jax.numpy`), None = the kernel on a TPU and
    `jax.numpy` off it. q and k reach the kernel in bfloat16, as the model
    computes them; a float32 caller that needs every bit takes "xla"."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        return _delta_step_xla(state, q, k, v, alpha, beta)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}; expected 'pallas', 'xla' or None")
    return _delta_step_pallas(state, q, k, v, alpha, beta,
                              interpret=jax.default_backend() != "tpu")
