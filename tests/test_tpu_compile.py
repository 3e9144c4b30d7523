"""The main-path Pallas kernels, compiled for a DESCRIBED TPU v5e at llama-1b widths,
and the XLA paged read at the benchmark's serving shapes (which passes over the
gathered window its optimized program may make).

Interpret mode (every other kernel test) cannot see what the chip's compiler
refuses: block shapes off the (8, 128) tiling, sub-tile scratch views, too much
VMEM. The TPU compiler is installed with jax, and compiles for a topology that is
described, not attached — so these cases guard every later PR at no chip time,
about a second each. Nothing runs: a compile that passes says nothing about
results (tests/test_paged_kernel.py, tests/test_quantization.py and
tests/test_flash_attention.py hold the kernels to their XLA oracles in interpret
mode; chip_smoke.py runs them on the chip).
"""

import math
import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

pytestmark = pytest.mark.kernels

# llama-1b attention widths (models/llama.py llama_1b).
HQ, HKV, D = 32, 8, 64
PAGES_PER_SLOT = 32


@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip; skipped only where the topology cannot be
    described (no TPU compiler, or another process holds libtpu's lock)."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — any failure to describe means skip
        pytest.skip(f"cannot describe a v5e topology here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip (the next run warns and recompiles):
    keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel in the compiled program"
    return compiled


@pytest.mark.parametrize("page_size", [16, 64])
@pytest.mark.parametrize("batch", [4, 8])
@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("s_block", [1, 5], ids=["decode", "verify5"])
def test_paged_attention_compiles_for_v5e(v5e, s_block, pool, batch, page_size):
    """`paged_decode_attention` (s=1) and `paged_verify_attention` (a speculative
    block of 5: rows = s*G = 20) at llama-1b widths, bf16 pools and int8 pools with
    scale operands. `interpret=False` is explicit: `default_backend()` is cpu here."""
    from accelerate_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_verify_attention,
    )

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    num_pages = batch * PAGES_PER_SLOT + 1
    pool_dtype = jnp.int8 if pool == "int8" else jnp.bfloat16
    args = [
        spec((batch, s_block, HQ, D), jnp.bfloat16),
        spec((num_pages, page_size, HKV, D), pool_dtype),
        spec((num_pages, page_size, HKV, D), pool_dtype),
        spec((batch, PAGES_PER_SLOT), jnp.int32),
        spec((batch, s_block), jnp.int32),
    ]
    kernel = paged_decode_attention if s_block == 1 else paged_verify_attention
    if pool == "int8":
        args += [spec((num_pages, HKV), jnp.float32)] * 2

        def fn(q, k, v, table, pos, k_scale, v_scale):
            return kernel(q, k, v, table, pos, interpret=False, k_scale=k_scale, v_scale=v_scale)
    else:

        def fn(q, k, v, table, pos):
            return kernel(q, k, v, table, pos, interpret=False)

    _compile(fn, *args)


# One layer of the benchmark's serving cells (chipbench/workloads/pythia-1.4b.*.json):
# 32 slots x 88 pages of 16 tokens, 16 heads of 128, a pool of 2,816 pages + scratch.
CELL_SLOTS, CELL_PAGES_PER_SLOT, CELL_PAGE_SIZE, CELL_HEADS, CELL_D = 32, 88, 16, 16, 128
CELL_WINDOW = CELL_SLOTS * CELL_PAGES_PER_SLOT * CELL_PAGE_SIZE * CELL_HEADS * CELL_D
_HLO_SELECT = re.compile(r" = \(?[a-z]+[0-9]*\[([0-9,]+)\]\S* select\(")


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("s_block", [1, 5], ids=["decode", "verify5"])
def test_xla_paged_read_has_no_window_sized_select(v5e, s_block, pool):
    """`slot_cache_attention(attention_impl="xla")` — `update_slot_cache`'s pool
    write and gather, then `dot_product_attention` — as the v5e's compiler leaves
    it. The gathered window ([32, 88, 16, 16, 128], 185 MB a tensor in bf16) is
    written by the two gathers and read by the two reductions; a `jnp.take` left at
    its default `mode="fill"` puts a `select(page id in range, page, NaN)` over
    both windows between them (`broadcast_select_fusion`: 369 MB read + 369 MB
    written a layer, 27 of a 64 ms decode step on the chip — PERF.md §6, PR 25)."""
    import flax.linen as nn

    from accelerate_tpu.ops.attention import slot_cache_attention

    num_pages = CELL_SLOTS * CELL_PAGES_PER_SLOT + 1

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, q, k, v, positions, table):
            return slot_cache_attention(
                self, q, k, v, CELL_PAGES_PER_SLOT * CELL_PAGE_SIZE, positions,
                page_table=table, page_size=CELL_PAGE_SIZE, num_pages=num_pages,
                attention_impl="xla", kv_cache_dtype=pool,
            )

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    layer = Layer()
    x = spec((CELL_SLOTS, s_block, CELL_HEADS, CELL_D), jnp.bfloat16)
    operands = (x, x, x, spec((CELL_SLOTS, s_block), jnp.int32),
                spec((CELL_SLOTS, CELL_PAGES_PER_SLOT), jnp.int32))
    cache = jax.eval_shape(lambda *a: layer.init(jax.random.key(0), *a), *operands)["cache"]
    cache = jax.tree_util.tree_map(lambda leaf: spec(leaf.shape, leaf.dtype), cache)

    def step(cache, *args):
        out, mutated = layer.apply({"cache": cache}, *args, mutable=["cache"])
        return out, mutated["cache"]

    compiled = jax.jit(step, donate_argnums=0).lower(cache, *operands).compile()
    window_selects = [
        line.strip()[:160]
        for line in compiled.as_text().splitlines()
        if (m := _HLO_SELECT.search(line))
        and math.prod(int(n) for n in m.group(1).split(",")) >= CELL_WINDOW
    ]
    assert not window_selects, window_selects
    # Decode, bf16: 1.895 GB with the fill, 1.156 GB without (the analysis counts
    # each in-place pool scatter as a pass over its pool besides). int8: 1.423 GB
    # with the fill, 1.417 GB without — and 2.257 GB if the int8 -> f32 convert
    # leaves the dequantize fusion (`update_slot_cache`'s barrier).
    bound = {("bf16", 1): 1.25e9, ("int8", 1): 1.5e9}.get((pool, s_block))
    if bound is not None:
        assert compiled.cost_analysis()["bytes accessed"] <= bound


@pytest.mark.parametrize("seq", [1024, 2048])
@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_flash_attention_compiles_for_v5e(v5e, direction, seq):
    """The training flash kernel at [2, seq, 32, 64] bf16, causal — the shape the
    trainer auto-dispatches to on TPU at seq >= 1024."""
    from accelerate_tpu.ops.flash_attention import flash_attention

    x = jax.ShapeDtypeStruct((2, seq, HQ, D), jnp.bfloat16, sharding=v5e)

    def forward(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    if direction == "forward":
        _compile(forward, x, x, x)
    else:
        _compile(jax.grad(lambda q, k, v: forward(q, k, v).astype(jnp.float32).sum(), (0, 1, 2)),
                 x, x, x)
