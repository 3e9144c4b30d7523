"""Manifold-constrained hyper-connections: a residual path of `n` streams mixed
around every sub-layer `F` (hyper-connections, arXiv:2409.19606; the
Sinkhorn-normalised form, arXiv:2512.24880).

A token's streams are ONE row `x = [X_0 | ... | X_{n-1}]` of `n * C` values —
the logical layout `[..., n * C]`, chosen from the compile for a v5e: `[T, n,
C]` bfloat16 puts `n = 4` on the second-minor axis, which is tiled 16 deep and
stored at four times its bytes; `[T, n * C]` is whole `(16, 128)` tiles at
`C = 3584`. A sub-layer replaces `h <- h + F(norm(h))` by

    r        = rsqrt(mean(x^2) + eps)                       (no learned scale)
    [p|q|m]  = (x . Phi) * r            Phi in R^{nC x (2n + n^2)}, float32
    H_pre    = sigmoid(a_pre p + b_pre)                     in R^n
    H_post   = 2 sigmoid(a_post q + b_post)                 in R^n
    H_res    = Sinkhorn(exp(clip(a_res mat(m) + b_res)))    in R^{n x n}
    u        = sum_j H_pre[j] X_j                           `hc_pre`
    X'_i     = sum_j H_res[i, j] X_j + H_post[i] F(norm(u)) `hc_post`

where a Sinkhorn turn divides each column by its sum and then each row by its
sum (`eps` in the divisor), `iters` times. `Phi` is stored TRANSPOSED, `phi_t`
`[2n + n^2, nC]`: 24 rows are three whole sublane tiles, where `[nC, 24]` pads
its minor axis to 128 lanes (7.3 MB a sub-layer for 1.4). The maps of a token
travel from `hc_pre` to `hc_post` as one packed float32 row of `MAP_LANES`
values, `[H_pre | H_post | H_res row-major | 0...]`: a row of 24 would be
stored 128 wide anyway, and here the pad is where it is counted.

Two forms of each op: `jax.numpy` (the oracle, and the path off a TPU) and a
Pallas kernel on a TPU, named `hc_pre` / `hc_post` so that a capture can tell
the mixes from everything else. Each kernel reads a block of rows once: the
products `x . Phi` run on the matrix unit with `Phi` split into three bfloat16
terms (exact float32 products of a bfloat16 stream), the Sinkhorn turns on
token-minor `[1, rows]` vectors, every lane a token. Maps, sums of squares and
mixes accumulate in float32. The kernels take bfloat16 streams, which every
served program's are; streams of another type run the `jax.numpy` form.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

#: Values of a token's packed maps row; `2n + n^2` of them are real.
MAP_LANES = 128
#: Rows a kernel's grid step reads (3.7 MB of a four-stream bfloat16 row of 14,336).
BLOCK_ROWS = 128
#: Columns of one stream a kernel's inner step mixes at a time.
_MIX_COLUMNS = 512


def map_count(n: int) -> int:
    return 2 * n + n * n


def unpack_maps(maps, n: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Packed maps `[..., MAP_LANES]` -> `(H_pre [..., n], H_post [..., n], H_res [..., n, n])`."""
    return (maps[..., :n], maps[..., n:2 * n],
            maps[..., 2 * n:map_count(n)].reshape(maps.shape[:-1] + (n, n)))


def _squash(raw, alpha, bias, n: int, clamp) -> Tuple[list, list, list]:
    """The three squashings on token-minor entries: `raw[k]`, `bias[k]` index a
    token's `2n + n^2` numbers in packed order, each `raw[k]` an array over
    tokens (tokens on the minor axis keep every lane busy). Returns `(H_pre
    [n], H_post [n], M [n][n] before its Sinkhorn turns)`."""
    h_pre = [jax.nn.sigmoid(alpha[0] * raw[j] + bias[j]) for j in range(n)]
    h_post = [2.0 * jax.nn.sigmoid(alpha[1] * raw[n + j] + bias[n + j]) for j in range(n)]
    m = [[jnp.exp(jnp.clip(alpha[2] * raw[2 * n + i * n + j] + bias[2 * n + i * n + j], clamp[0], clamp[1]))
          for j in range(n)] for i in range(n)]
    return h_pre, h_post, m


def _sinkhorn_turn(m: list, eps: float) -> list:
    """Each column of `m [n][n]` by its sum, then each row by its sum."""
    n = len(m)
    total = lambda entries: functools.reduce(lambda a, b: a + b, entries)  # noqa: E731
    columns = [1.0 / (total([m[i][j] for i in range(n)]) + eps) for j in range(n)]
    m = [[m[i][j] * columns[j] for j in range(n)] for i in range(n)]
    rows = [1.0 / (total(m[i]) + eps) for i in range(n)]
    return [[m[i][j] * rows[i] for j in range(n)] for i in range(n)]


def hc_maps(x, phi_t, alpha, bias, *, n: int, iters: int, eps: float, clamp=(-30.0, 30.0)):
    """x `[..., n * C]` -> the packed maps `[..., MAP_LANES]`, float32, in
    `jax.numpy`: the kernel's arithmetic on token-minor arrays, the Sinkhorn
    turns a loop of `iters` trips."""
    count = map_count(n)
    if phi_t.shape != (count, x.shape[-1]) or count > MAP_LANES:
        raise ValueError(f"phi_t {phi_t.shape} is not [{count}, {x.shape[-1]}] for {n} streams")
    x32 = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1) + eps)
    raw = jnp.einsum("mk,...k->m...", phi_t.astype(jnp.float32), x32,
                     precision=jax.lax.Precision.HIGHEST) * r  # token-minor [2n + n^2, ...]
    h_pre, h_post, m = _squash(raw, alpha.astype(jnp.float32), bias.astype(jnp.float32), n, clamp)
    m = jax.lax.fori_loop(0, iters, lambda _, m: _sinkhorn_turn(m, eps), m)
    rows = h_pre + h_post + [m[i][j] for i in range(n) for j in range(n)]
    rows += [jnp.zeros_like(rows[0])] * (MAP_LANES - count)
    return jnp.stack(rows, axis=-1)


def _streams(x, n: int) -> list:
    c = x.shape[-1] // n
    return [x[..., j * c:(j + 1) * c] for j in range(n)]


def _hc_pre_xla(x, phi_t, alpha, bias, n, iters, eps, clamp):
    maps = hc_maps(x, phi_t, alpha, bias, n=n, iters=iters, eps=eps, clamp=clamp)
    u = sum(maps[..., j:j + 1] * s.astype(jnp.float32) for j, s in enumerate(_streams(x, n)))
    return u.astype(x.dtype), maps


def _hc_post_xla(x, y, maps, n):
    streams = [s.astype(jnp.float32) for s in _streams(x, n)]
    y32 = y.astype(jnp.float32)
    out = []
    for i in range(n):
        acc = maps[..., n + i:n + i + 1] * y32
        for j in range(n):
            k = 2 * n + i * n + j
            acc = acc + maps[..., k:k + 1] * streams[j]
        out.append(acc)
    return jnp.concatenate(out, axis=-1).astype(x.dtype)


# ------------------------------------------------------------------ the kernels
def _split3(w):
    """A float32 array as three bfloat16 terms whose sum is it (24 bits of mantissa)."""
    hi = w.astype(jnp.bfloat16)
    rest = w - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _exact_nt(w, x):
    """`w [m, k]` float32 times a bfloat16 `x [rows, k]` transposed -> `[m, rows]`
    float32, to float32's precision out of three single-pass bfloat16 products
    (`x` is one term already)."""
    contract = (((1,), (1,)), ((), ()))
    hi, mid, low = (jax.lax.dot_general(term, x, contract, preferred_element_type=jnp.float32) for term in _split3(w))
    return hi + (mid + low)


def _hc_pre_kernel(x_ref, phi_ref, coef_ref, u_ref, maps_ref, lanes_ref, *, n, iters, eps, clamp):
    count = map_count(n)
    rows, width = x_ref.shape
    c = width // n
    raw = jnp.zeros((count, rows), jnp.float32)
    squares = jnp.zeros((rows, 1), jnp.float32)
    for j in range(n):
        xj = x_ref[:, j * c:(j + 1) * c]
        raw = raw + _exact_nt(phi_ref[:, j * c:(j + 1) * c], xj)
        x32 = xj.astype(jnp.float32)
        squares = squares + jnp.sum(x32 * x32, axis=-1, keepdims=True)
    # the sums of squares join the products on the token-minor side through a whole-tile transpose
    r = jax.lax.rsqrt(jnp.broadcast_to(squares, (rows, MAP_LANES)).T[0:1, :] / width + eps)  # [1, rows]
    coef = coef_ref[...]  # [count, 128]: column 0 the biases, column 1 the three alphas
    raw = raw * r
    h_pre, h_post, m = _squash([raw[k:k + 1, :] for k in range(count)], [coef[k:k + 1, 1:2] for k in range(3)],
                               [coef[k:k + 1, 0:1] for k in range(count)], n, clamp)
    m = jax.lax.fori_loop(0, iters, lambda _, m: _sinkhorn_turn(m, eps), m)  # `[1, rows]` vectors: a lane a token
    vectors = h_pre + h_post + [m[i][j] for i in range(n) for j in range(n)]
    lanes_ref[...] = jnp.zeros_like(lanes_ref)
    for k, vector in enumerate(vectors):
        lanes_ref[k:k + 1, :] = vector
    maps = lanes_ref[...].T  # [rows, MAP_LANES]
    maps_ref[...] = maps
    for start in range(0, c, _MIX_COLUMNS):
        stop = min(start + _MIX_COLUMNS, c)
        acc = maps[:, 0:1] * x_ref[:, start:stop].astype(jnp.float32)
        for j in range(1, n):
            acc = acc + maps[:, j:j + 1] * x_ref[:, j * c + start:j * c + stop].astype(jnp.float32)
        u_ref[:, start:stop] = acc.astype(u_ref.dtype)


def _hc_post_kernel(x_ref, y_ref, maps_ref, o_ref, *, n):
    c = y_ref.shape[-1]
    maps = maps_ref[...]
    for start in range(0, c, _MIX_COLUMNS):
        stop = min(start + _MIX_COLUMNS, c)
        y32 = y_ref[:, start:stop].astype(jnp.float32)
        streams = [x_ref[:, j * c + start:j * c + stop].astype(jnp.float32) for j in range(n)]
        for i in range(n):
            acc = maps[:, n + i:n + i + 1] * y32
            for j in range(n):
                k = 2 * n + i * n + j
                acc = acc + maps[:, k:k + 1] * streams[j]
            o_ref[:, i * c + start:i * c + stop] = acc.astype(o_ref.dtype)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=("parallel",), vmem_limit_bytes=64 * 1024 * 1024)


def _hc_pre_pallas(x, phi_t, alpha, bias, n, iters, eps, clamp, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    count = map_count(n)
    lead, width = x.shape[:-1], x.shape[-1]
    c = width // n
    rows = x.reshape(-1, width)
    total = rows.shape[0]
    coef = jnp.zeros((count, MAP_LANES), jnp.float32)
    coef = coef.at[:, 0].set(bias.astype(jnp.float32)).at[:3, 1].set(alpha.astype(jnp.float32))
    block = lambda cols: pl.BlockSpec((BLOCK_ROWS, cols), lambda i: (i, 0))  # noqa: E731
    whole = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))  # noqa: E731
    u, maps = pl.pallas_call(
        functools.partial(_hc_pre_kernel, n=n, iters=iters, eps=eps, clamp=clamp),
        grid=(pl.cdiv(total, BLOCK_ROWS),),
        in_specs=[block(width), whole((count, width)), whole((count, MAP_LANES))],
        out_specs=[block(c), block(MAP_LANES)],
        out_shape=[jax.ShapeDtypeStruct((total, c), x.dtype),
                   jax.ShapeDtypeStruct((total, MAP_LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((MAP_LANES, BLOCK_ROWS), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="hc_pre",
    )(rows, phi_t.astype(jnp.float32), coef)
    return u.reshape(lead + (c,)), maps.reshape(lead + (MAP_LANES,))


def _hc_post_pallas(x, y, maps, n, interpret: bool):
    from jax.experimental import pallas as pl

    lead, width = x.shape[:-1], x.shape[-1]
    c = width // n
    rows = x.reshape(-1, width)
    total = rows.shape[0]
    block = lambda cols: pl.BlockSpec((BLOCK_ROWS, cols), lambda i: (i, 0))  # noqa: E731
    out = pl.pallas_call(
        functools.partial(_hc_post_kernel, n=n),
        grid=(pl.cdiv(total, BLOCK_ROWS),),
        in_specs=[block(width), block(c), block(MAP_LANES)],
        out_specs=block(width),
        out_shape=jax.ShapeDtypeStruct((total, width), x.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="hc_post",
    )(rows, y.reshape(-1, c).astype(x.dtype), maps.reshape(-1, MAP_LANES))
    return out.reshape(lead + (width,))


@functools.lru_cache(maxsize=None)
def _kernel_calls():
    """The two kernels' calls, traced and lowered ONCE for all the sub-layers of
    a program that share their shapes (two a layer): a call a sub-layer cost the
    prompt-heavy cell's set-up most of the 82 s its warm-up took with every
    program already compiled (PERF.md section 6, PR 40)."""
    return (jax.jit(_hc_pre_pallas, static_argnums=(4, 5, 6, 7, 8)),
            jax.jit(_hc_post_pallas, static_argnums=(3, 4)))


def _resolve(impl: Optional[str], dtype) -> str:
    if impl is None:
        return "pallas" if jax.default_backend() == "tpu" and dtype == jnp.bfloat16 else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown impl {impl!r}; expected 'pallas', 'xla' or None")
    if impl == "pallas" and dtype != jnp.bfloat16:
        raise ValueError(f"impl 'pallas' takes bfloat16 streams, not {jnp.dtype(dtype).name}: use 'xla' or None")
    return impl


def hc_pre(x, phi_t, alpha, bias, *, n: int, iters: int, eps: float, clamp=(-30.0, 30.0),
           impl: Optional[str] = None):
    """x `[..., n * C]`, `phi_t [2n + n^2, n * C]`, `alpha [3]` (pre, post,
    res), `bias [2n + n^2]` (packed order) -> `(u [..., C]` in x's type, the
    packed maps `[..., MAP_LANES]` float32`)`.

    ``impl``: "pallas" (the kernel `hc_pre`; compiled on a TPU, the interpreter
    elsewhere; bfloat16 streams only), "xla" (`jax.numpy`), None = the kernel
    for bfloat16 streams on a TPU and `jax.numpy` otherwise."""
    clamp = (float(clamp[0]), float(clamp[1]))
    with jax.named_scope("hc_pre"):
        if _resolve(impl, x.dtype) == "xla":
            return _hc_pre_xla(x, phi_t, alpha, bias, n, iters, eps, clamp)
        return _kernel_calls()[0](x, phi_t, alpha, bias, n, iters, eps, clamp, jax.default_backend() != "tpu")


def hc_post(x, y, maps, *, n: int, impl: Optional[str] = None):
    """x `[..., n * C]`, the sub-layer's output y `[..., C]`, `hc_pre`'s maps
    -> the streams after the sub-layer, `[..., n * C]` in x's type."""
    with jax.named_scope("hc_post"):
        if _resolve(impl, x.dtype) == "xla":
            return _hc_post_xla(x, y, maps, n)
        return _kernel_calls()[1](x, y, maps, n, jax.default_backend() != "tpu")
