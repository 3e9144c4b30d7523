"""The selective state-space recurrence of Mamba-2 (SSD: Dao & Gu,
arXiv:2405.21060) with a scalar decay a head: what a state-space mixer computes
where a softmax layer reads a cache of keys and values.

A head keeps a matrix ``H`` of ``[head_dim, state_dim]`` float32 instead of a
growing cache. Token ``t`` brings the head's input ``x_t`` (``head_dim``), a
step ``dt_t > 0`` (after its softplus) and, shared by the heads of a GROUP,
``B_t`` and ``C_t`` (``state_dim``); ``A < 0`` is the head's own:

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T
    y_t = H_t C_t

(the skip ``D x_t``, the gate and the grouped norm are the model's). Two forms
of the same recurrence, both taking and returning ``H``:

  - `ssd_chunked` (prefill): chunks of `CHUNK` tokens in the paper's dual form
    — inside a chunk the products ``C B^T`` under the decay mask, a chunk's own
    state from its inputs, and only the chunk boundary carries ``H`` — so a
    prompt costs matmuls, not a scan over tokens. A position with ``dt = 0``
    (decay 1, no input) leaves ``H`` as it found it, which is how a bucket's
    padding is masked.
  - `ssm_step` (decode): one token a row. The step reads ``H`` once and writes
    it once; on a TPU it is the Pallas kernel `ssm_step`, elsewhere the same
    arithmetic in `jax.numpy`.

How ``H`` is stored (`to_slot_layout`): ``[rows, state_dim, heads * head_dim]``
float32 — TRANSPOSED, the state axis on the sublanes and every head's inputs
side by side on the lanes (4,096 = 32 tiles at the published 32 heads of 128,
256 rows = 32 tiles: the array is stored at its bytes; the compile for a
described v5e says so). The slot axis is third from the back, where
`utils/operations` finds a by-slot leaf. In this layout the head's decay and
``dt x`` are numbers a COLUMN (lane-dense rows, broadcast down the sublanes for
nothing) and ``y`` a sum down the sublanes; only ``B`` and ``C`` are numbers a
row, and a row's number over its group's columns is what XLA materialises at
the size of ``H`` (seen in the compile of the step written head-major: two
broadcasts of 336 MB a layer, written and read back, 6 passes over ``H`` where 2
are needed). The kernel makes them in fast memory with one small matmul against
a 0/1 matrix (exact: one bfloat16 term a column), as `ops/delta_rule.py`'s does
for its keys.

All decays, the state and every accumulation into it are float32; x, B and C
come in the model's compute type.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

#: Tokens a chunk of the prefill form where the caller names none
#: (`mamba_chunk_size` of the published configurations this was written for).
CHUNK = 128

_HIGHEST = jax.lax.Precision.HIGHEST


def to_slot_layout(state):
    """``[rows, heads, head_dim, state_dim]`` -> ``[rows, state_dim, heads * head_dim]``."""
    rows, heads, p, n = state.shape
    return state.transpose(0, 3, 1, 2).reshape(rows, n, heads * p)


def from_slot_layout(state, heads: int):
    """The inverse of `to_slot_layout`."""
    rows, n, width = state.shape
    return state.reshape(rows, n, heads, width // heads).transpose(0, 2, 3, 1)


# ------------------------------------------------------------- the prefill form
def ssd_chunked(x, dt, a, b_in, c_in, state, *, chunk: int = CHUNK, precision=_HIGHEST):
    """The recurrence over a block of tokens, chunk by chunk.

    x [B, T, H, P], dt [B, T, H] float32 (0 at a padded position), a [H]
    float32 (negative), b_in, c_in [B, T, G, N] (H a multiple of G), state
    [B, H, P, N] float32 (the state before the block). Returns (y [B, T, H, P]
    float32, the state after the block). T need not be a multiple of ``chunk``:
    the tail is padded with positions that leave the state alone.

    Inside a chunk, with ``g`` the running sum of ``dt A`` and ``X = dt x``:
    ``y = tril((C B^T) exp(g_i - g_j)) X + exp(g) C H_in`` and
    ``H_out = exp(g_L) H_in + (B exp(g_L - g))^T X``."""
    bsz, t, h, p = x.shape
    groups, n = b_in.shape[2], b_in.shape[3]
    pad = -t % chunk
    if pad:
        widths = ((0, 0), (0, pad), (0, 0))
        x, b_in, c_in = (jnp.pad(v, widths + ((0, 0),)) for v in (x, b_in, c_in))
        dt = jnp.pad(dt, widths)
    count = (t + pad) // chunk
    per = h // groups

    def chunks(v):  # [B, T, ...] -> [count, B, chunk, ...]
        v = v.astype(jnp.float32).reshape((bsz, count, chunk) + v.shape[2:])
        return jnp.moveaxis(v, 1, 0)

    dt = chunks(dt)  # [n, B, L, H]
    xs = (chunks(x) * dt[..., None]).reshape(count, bsz, chunk, groups, per, p)  # X = dt x, heads by group
    bs, cs = chunks(b_in), chunks(c_in)  # [n, B, L, G, N]
    g = jnp.cumsum(dt * a.astype(jnp.float32), axis=2).reshape(count, bsz, chunk, groups, per)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]
    # exp only where i >= j: above the diagonal the difference is positive and unbounded
    decay = jnp.exp(jnp.where(lower, g[:, :, :, None] - g[:, :, None, :], -jnp.inf))  # [n, B, L, L, G, per]
    cb = jnp.einsum("zblgn,zbsgn->zblsg", cs, bs, precision=precision)
    y_within = jnp.einsum("zblsgh,zbsghp->zblghp", cb[..., None] * decay, xs, precision=precision)
    to_end = jnp.exp(g[:, :, -1:] - g)  # [n, B, L, G, per]
    own = jnp.einsum("zblgn,zblghp->zbghpn", bs, xs * to_end[..., None], precision=precision)
    carry_decay = jnp.exp(g[:, :, -1])[..., None, None]  # [n, B, G, per, 1, 1]
    from_start = jnp.exp(g)  # [n, B, L, G, per]

    def one_chunk(s, inputs):
        own_n, decay_n, c_n, from_start_n = inputs
        y_carried = jnp.einsum("blgn,bghpn->blghp", c_n, s, precision=precision) * from_start_n[..., None]
        return decay_n * s + own_n, y_carried

    before = state.astype(jnp.float32).reshape(bsz, groups, per, p, n)
    after, y_carried = jax.lax.scan(one_chunk, before, (own, carry_decay, cs, from_start))
    y = jnp.moveaxis(y_within + y_carried, 0, 1).reshape(bsz, count * chunk, h, p)
    return y[:, :t], after.reshape(bsz, h, p, n)


# -------------------------------------------------------------- the decode form
def _columns(x, width: int):
    """A number a head (or a group) -> that head's (group's) columns: [..., H] -> [..., H * width]."""
    return jnp.repeat(x, width, axis=-1)


def _row_operands(x, dt, a):
    """The per-head numbers of a step over their columns, two ``[B, 1, H * P]``
    float32 rows which XLA makes for nothing: the decay ``exp(dt A)`` and ``dt x``."""
    bsz, h, p = x.shape
    decay = _columns(jnp.exp(dt * a.astype(jnp.float32)), p)[:, None, :]
    return decay, (dt[..., None] * x.astype(jnp.float32)).reshape(bsz, 1, h * p)


def _ssm_step_xla(state, x, dt, a, b_in, c_in):
    """`ssm_step` in `jax.numpy`, on the slot layout."""
    bsz, h, p = x.shape
    groups = b_in.shape[1]
    decay, dtx = _row_operands(x, dt, a)
    b_cols = _columns(jnp.swapaxes(b_in.astype(jnp.float32), 1, 2), h // groups * p)  # [B, N, H * P]
    c_cols = _columns(jnp.swapaxes(c_in.astype(jnp.float32), 1, 2), h // groups * p)
    new = decay * state + b_cols * dtx
    return jnp.sum(new * c_cols, axis=1).reshape(bsz, h, p), new


def _ssm_step_kernel(s_ref, bt_ref, ct_ref, e_ref, a_ref, u_ref, s_out, y_out):
    """One row (a slot), one block of columns: H in, H out, once each."""
    e = e_ref[...]  # [G, cols] 0/1: column c belongs to group c // (cols of a group)
    # a group's B and C over its own columns, made in fast memory: one exact term a column
    b_cols = jnp.dot(bt_ref[0], e, preferred_element_type=jnp.float32)  # [N, cols]
    c_cols = jnp.dot(ct_ref[0], e, preferred_element_type=jnp.float32)
    new = a_ref[0] * s_ref[0] + b_cols * u_ref[0]
    s_out[0] = new
    y_out[0] = jnp.sum(new * c_cols, axis=0, keepdims=True)


def _ssm_step_pallas(state, x, dt, a, b_in, c_in, interpret: bool = False):
    """`ssm_step` as the kernel `ssm_step`: grid (rows, groups), a step
    streaming one row's ``[state_dim, a group's columns]`` of H through fast
    memory — 2.1 MB a block at 256 x 2,048 (blocks of 512 to 4,096 columns read
    the same on a v5e: 1,006-1,022 us a layer at 80 slots, PERF.md section 5)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, h, p = x.shape
    groups, n = b_in.shape[1], b_in.shape[2]
    width = h * p
    cols = width // groups
    decay, dtx = _row_operands(x, dt, a)
    bt = jnp.swapaxes(b_in, 1, 2).astype(jnp.bfloat16)  # [B, N, G]
    ct = jnp.swapaxes(c_in, 1, 2).astype(jnp.bfloat16)
    expand = (jnp.arange(width)[None, :] // (width // groups) == jnp.arange(groups)[:, None]).astype(jnp.bfloat16)

    row = lambda shape: pl.BlockSpec((1,) + shape, lambda i, j: (i, 0, j))  # noqa: E731
    groups_spec = pl.BlockSpec((1, n, groups), lambda i, j: (i, 0, 0))
    new_state, y = pl.pallas_call(
        _ssm_step_kernel,
        grid=(bsz, width // cols),
        in_specs=[row((n, cols)), groups_spec, groups_spec,
                  pl.BlockSpec((groups, cols), lambda i, j: (0, j)),
                  row((1, cols)), row((1, cols))],
        out_specs=[row((n, cols)), row((1, cols))],
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((bsz, 1, width), jnp.float32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="ssm_step",
    )(state, bt, ct, expand, decay, dtx)
    return y.reshape(bsz, h, p), new_state


def ssm_step(x, dt, a, b_in, c_in, state, *, impl: Optional[str] = None):
    """One token a row. x [B, H, P], dt [B, H] float32, a [H] float32, b_in,
    c_in [B, G, N], state [B, N, H * P] float32 (`to_slot_layout`). Returns
    (y [B, H, P] float32, the new state, same layout).

    ``impl``: "pallas" (the kernel `ssm_step`; compiled on a TPU, the
    interpreter elsewhere), "xla" (`jax.numpy`), None = the kernel on a TPU and
    `jax.numpy` off it. B and C reach the kernel in bfloat16, as the model
    computes them; a float32 caller that needs every bit takes "xla"."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        return _ssm_step_xla(state, x, dt, a, b_in, c_in)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}; expected 'pallas', 'xla' or None")
    return _ssm_step_pallas(state, x, dt, a, b_in, c_in, interpret=jax.default_backend() != "tpu")
