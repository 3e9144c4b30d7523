"""Inference quantization: int8 weight-only matmuls and the quantized KV page
pool — the serving-side bandwidth multipliers (ROADMAP item 5).

Decode is HBM-bandwidth-bound: every step streams the weights and the live KV
pages, so bytes-per-value is a direct throughput multiplier. Two independent
seams, both selected by engine/model config and both keeping the
compiled-once discipline (dtypes are static config; every scale is a traced
ARRAY operand, never a Python scalar — TPU117 lints the violation):

  - **Weight-only int8** (`weight_dtype="int8"`): per-output-channel symmetric
    scales computed ONCE at weight-load/`swap_weights` time
    (`quantize_params_int8` — the engine's `params` setter calls it), applied
    in the matmul epilogue by a flax method interceptor (`weight_autocast`,
    the same mechanism as `fp8_autocast` in `ops/fp8.py`): every bound
    `nn.Dense.__call__` whose kernel is a quantized entry computes
    ``(x @ q) * scale`` — the int8 kernel streams from HBM at 1 byte/value,
    the cast fuses into the matmul read, and the scale is one fused
    elementwise epilogue. Per-output-channel scaling makes the epilogue EXACT
    with respect to dequantize-then-matmul.
  - **Quantized KV page pool** (`kv_cache_dtype="int8" | "fp8_e4m3"`): the
    paged slot cache (`ops/attention._write_slot_pool`) stores pages in the
    quantized dtype with per-page-per-head scales riding in a parallel
    ``[num_pages, heads]`` pool array inside the same flax "cache" collection.
    The XLA read dequantizes each block it gathers (the parity oracle); the Pallas
    paged kernels (`ops/paged_attention.py`) fuse the dequant into the
    page-streaming online-softmax loop, so quantized decode moves int8/fp8
    bytes per page, not bf16.

Page-scale maintenance (the part unique to an incrementally-written cache):
a page's scale can only be finalized when its content stops changing, but
decode appends one token at a time. The write path therefore keeps the
invariant ``stored_q * scale == value`` by construction: a write at page
offset 0 RESETS the page's scale (fresh page, stale content from a previous
occupant must not pin an old range); every write raises the scale to cover
the incoming token's amax (`scale = max(scale, amax/qmax)`); and when the
scale grows, the page's EXISTING rows are requantized in the same dispatch
(`ratio = old/new`, one page-sized read-modify-write — bytes proportional to
the pages touched this step, not the pool). fp8 (e4m3) follows the
`ops/fp8.py` scaled-cast machinery (`E4M3_MAX` saturating casts); int8 is
symmetric round-to-nearest at qmax 127.

int4 weight/KV packing is explicitly out of scope here (docs/limitations.md);
`utils/quantization.py` keeps the bnb-parity int4/nf4 *storage* path for
loading, which `_params_resolver` dequantizes in-program.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .fp8 import E4M3, E4M3_MAX

#: Supported KV page-pool storage dtypes. "bf16" means UNQUANTIZED — pages
#: keep the model's compute dtype (bf16 on accelerators, f32 in CPU tests).
KV_CACHE_DTYPES = ("bf16", "int8", "fp8_e4m3")

#: Supported weight storage dtypes for the serving engines.
WEIGHT_DTYPES = ("bf16", "int8")

#: Scale floor: avoids div-by-zero for all-zero pages/channels without
#: perturbing any real scale (activations/weights sit orders of magnitude up).
_TINY = 1e-12

INT8_MAX = 127.0


def kv_quant_spec(kv_cache_dtype: str) -> Optional[Tuple[Any, float]]:
    """``(storage dtype, qmax)`` for a quantized KV cache dtype, or None for
    the unquantized "bf16" default. Raises on anything off the supported set
    (the same set TPU117 lints literals against)."""
    if kv_cache_dtype == "bf16":
        return None
    if kv_cache_dtype == "int8":
        return jnp.int8, INT8_MAX
    if kv_cache_dtype == "fp8_e4m3":
        return E4M3, E4M3_MAX
    raise ValueError(
        f"unknown kv_cache_dtype {kv_cache_dtype!r}; expected one of {KV_CACHE_DTYPES}"
    )


def kv_spec_for_dtype(dtype) -> Optional[Tuple[Any, float]]:
    """``(dtype, qmax)`` for a pool leaf's STORAGE dtype (the inverse lookup
    of `kv_quant_spec` used by the cache-pytree gather/scatter helpers), or
    None for unquantized float pools."""
    if dtype == jnp.int8:
        return jnp.int8, INT8_MAX
    if dtype == E4M3:
        return E4M3, E4M3_MAX
    return None


def _cast_quantized(x, dtype, qmax):
    """fp32 values already divided by their scale -> storage dtype. int8
    rounds to nearest then clips; fp8 saturates (the `ops/fp8.py`
    `_quantize_with_scale` behavior) — the cast itself rounds."""
    if dtype == jnp.int8:
        return jnp.clip(jnp.round(x), -qmax, qmax).astype(jnp.int8)
    return jnp.clip(x, -qmax, qmax).astype(dtype)


def quantize_kv(x, scale, dtype, qmax):
    """Quantize K/V values against a broadcastable traced `scale` array."""
    return _cast_quantized(x.astype(jnp.float32) / jnp.maximum(scale, _TINY), dtype, qmax)


def dequantize_kv(q, scale, dtype=jnp.float32):
    """``q * scale`` in fp32, cast to the requested compute dtype. `scale`
    must be a traced array broadcastable against `q` (TPU117)."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def requantize_kv(q, ratio, dtype, qmax):
    """Re-express stored quantized values under a grown scale:
    ``q_new = q_old * (old_scale / new_scale)``. `ratio` <= 1 for real
    growth; a freshly-reset page carries ratio 0, which zeroes its stale
    content in the same op."""
    return _cast_quantized(q.astype(jnp.float32) * ratio, dtype, qmax)


def quantized_pool_write(pool, scale, x, pid, off, spec):
    """The quantized half of the paged cache's token write: scatter this
    dispatch's ``[B, s, h, d]`` K or V rows into the quantized page pool
    through ``(pid, off)`` (``[B, s]`` pool-page ids / in-page offsets),
    maintaining the per-page-per-head `scale` array ``[num_pages, h]``.

    Invariant on exit: every live row of every touched page satisfies
    ``dequantize(stored, scale[page, head]) ~= written value`` —
      1. a write at offset 0 resets the page's scale (new occupant),
      2. the scale rises to cover each incoming token (scatter-max),
      3. pages whose scale changed are requantized in place (ratio =
         old/new; bytes proportional to pages touched, not the pool).
    Duplicate page ids across rows only occur for the scratch page, whose
    content is never attended. All arrays are traced operands."""
    dtype, qmax = spec
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)  # [B, s, h]
    # (1) reset: route non-offset-0 writes' reset at the scratch page, whose
    # scale is meaningless (its rows sit above every live position).
    reset_pid = jnp.where(off == 0, pid, 0)
    scale_after_reset = scale.at[reset_pid].set(0.0)
    # (2) raise: every token this dispatch writes is representable.
    new_scale = scale_after_reset.at[pid].max(amax / qmax)
    safe_scale = jnp.maximum(new_scale, _TINY)
    # (3) requantize the touched pages under their (possibly) grown scale.
    ratio = scale_after_reset / safe_scale  # [num_pages, h]
    touched = pool[pid]  # [B, s, page_size, h, d]
    requant = requantize_kv(touched, ratio[pid][:, :, None, :, None], dtype, qmax)
    pool = pool.at[pid].set(requant)
    q = quantize_kv(x, safe_scale[pid][..., None], dtype, qmax)
    pool = pool.at[pid, off].set(q)
    return pool, new_scale


def quantize_kv_pages(blocks, spec):
    """Whole-page quantization for the insert path (`tree_scatter_pages`):
    `blocks` ``[P, ..., page_size, h, d]`` float pages -> (quantized blocks,
    per-page-per-head scales ``[P, ..., h]``). Scale covers the page's amax,
    so a freshly-prefilled page round-trips within half a quantization step."""
    dtype, qmax = spec
    amax = jnp.max(jnp.abs(blocks.astype(jnp.float32)), axis=(-3, -1))  # [P, ..., h]
    scale = amax / qmax
    q = quantize_kv(blocks, scale[..., None, :, None], dtype, qmax)
    return q, scale


def dequantize_kv_pages(blocks, scale, dtype):
    """Inverse of `quantize_kv_pages` for gathered pages: `blocks`
    ``[..., P, page_size, h, d]`` quantized, `scale` ``[..., P, h]``."""
    return dequantize_kv(blocks, scale[..., :, None, :, None], dtype)


# ------------------------------------------------------------------- weights

#: Key names of a quantized kernel entry (a plain dict so the params tree
#: stays a vanilla pytree for jit/device_put/save_pytree).
_QKEYS = frozenset(("q", "scale"))


def is_quantized_kernel(value) -> bool:
    """True for a `quantize_weight_int8` entry ({"q": int8, "scale": f32})."""
    return isinstance(value, dict) and set(value.keys()) == set(_QKEYS)


@jax.jit
def _quantize_int8(w):
    w32 = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=tuple(range(w32.ndim - 1)))
    scale = absmax / INT8_MAX
    q = jnp.clip(jnp.round(w32 / jnp.maximum(scale, _TINY)), -INT8_MAX, INT8_MAX)
    return q.astype(jnp.int8), scale


def quantize_weight_int8(w) -> Dict[str, Any]:
    """Per-output-channel symmetric int8: scales over every axis but the last
    (the output-feature axis of a flax Dense kernel ``[K, N]``), computed once
    at load time. ``w ~= q * scale`` with `scale` shaped ``[N]``. One compiled
    program a shape: run operation by operation, a head of 261,120 x 5,120
    held several float32 copies of itself (5.35 GB each) at once and a chip
    that fits the int8 model could not load it."""
    q, scale = _quantize_int8(jnp.asarray(w))
    return {"q": q, "scale": scale}


def dequantize_weight_int8(entry, dtype=jnp.float32):
    return (entry["q"].astype(jnp.float32) * entry["scale"]).astype(dtype)


def quantize_params_int8(params):
    """Params-tree transform for the serving engines: every floating Dense
    kernel (path leaf named ``kernel``, ndim >= 2) becomes a quantized entry;
    embeddings, norms, biases and already-quantized entries pass through
    untouched (idempotent — re-applying on swap never double-quantizes).
    The module tree is untouched: `weight_autocast` intercepts the consuming
    ``nn.Dense.__call__`` at trace time."""
    def _q(path, leaf):
        name = str(getattr(path[-1], "key", getattr(path[-1], "name", path[-1])))
        if (
            name == "kernel"
            and getattr(leaf, "ndim", 0) >= 2
            and jnp.issubdtype(jnp.result_type(leaf), jnp.floating)  # no transfer of a host leaf
        ):
            return quantize_weight_int8(leaf)
        return leaf

    return jax.tree_util.tree_map_with_path(_q, params)


def params_nbytes(params) -> int:
    """Actual stored bytes of a (possibly quantized) params tree — what the
    bench reports as weight footprint."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        total += int(leaf.size) * np.dtype(leaf.dtype).itemsize
    return total


def _int8_dense_apply(module, x):
    """Compute a bound `nn.Dense` whose kernel is a quantized entry: the int8
    matrix feeds the MXU in the compute dtype (the cast fuses into the HBM
    read) and the per-output-channel scale lands in the epilogue — exact
    w.r.t. dequantize-then-matmul because the scale is constant per output
    column of the dot."""
    entry = module.get_variable("params", "kernel")
    q, scale = entry["q"], entry["scale"]
    contract = (((x.ndim - 1,), (0,)), ((), ()))
    y = jax.lax.dot_general(
        x, q.astype(x.dtype), contract, preferred_element_type=jnp.float32
    )
    y = (y * scale.astype(jnp.float32)).astype(x.dtype)
    if module.use_bias:
        y = y + module.get_variable("params", "bias").astype(y.dtype)
    return y


@contextlib.contextmanager
def weight_autocast(weight_dtype: str = "int8"):
    """Run flax applies with quantized-weight matmuls: every bound
    `nn.Dense.__call__` whose kernel is a `quantize_params_int8` entry uses
    the int8 epilogue path (the `fp8_autocast` interceptor pattern,
    ops/fp8.py). "bf16" is a no-op context so call sites can wrap
    unconditionally; dense (unquantized) kernels fall through untouched, so
    partially-quantized trees and init passes keep working."""
    if weight_dtype not in WEIGHT_DTYPES:
        raise ValueError(
            f"unknown weight_dtype {weight_dtype!r}; expected one of {WEIGHT_DTYPES}"
        )
    if weight_dtype == "bf16":
        yield
        return
    import flax.linen as nn

    def interceptor(next_fun, args, kwargs, context):
        if isinstance(context.module, nn.Dense) and context.method_name == "__call__":
            if context.module.has_variable("params", "kernel") and is_quantized_kernel(
                context.module.get_variable("params", "kernel")
            ):
                return _int8_dense_apply(context.module, args[0])
        return next_fun(*args, **kwargs)

    with nn.intercept_methods(interceptor):
        yield
