"""The main-path Pallas kernels, compiled for a DESCRIBED TPU v5e at llama-1b widths.

Interpret mode (every other kernel test) cannot see what the chip's compiler
refuses: block shapes off the (8, 128) tiling, sub-tile scratch views, too much
VMEM. The TPU compiler is installed with jax, and compiles for a topology that is
described, not attached — so these cases guard every later PR at no chip time,
about a second each. Nothing runs: a compile that passes says nothing about
results (tests/test_paged_kernel.py, tests/test_quantization.py and
tests/test_flash_attention.py hold the kernels to their XLA oracles in interpret
mode; chip_smoke.py runs them on the chip).
"""

import os

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
