"""The main-path Pallas kernels, compiled for a DESCRIBED TPU v5e at llama-1b widths,
and the XLA paged read at the benchmark's serving shapes (which arrays its
optimized program may make: blocks of live pages, never the window).

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


#: (slots, pages a slot, page size, query heads, KV heads, head_dim) the page-walk kernel is compiled at.
PAGED_SHAPES = {
    # llama-1b's widths: heads of 64 are half a lane row, so these pools are STAGED (`kernel_stages_pool`)
    "llama_1b-4-16": (4, PAGES_PER_SLOT, 16, HQ, HKV, D),
    "llama_1b-8-16": (8, PAGES_PER_SLOT, 16, HQ, HKV, D),
    "llama_1b-4-64": (4, PAGES_PER_SLOT, 64, HQ, HKV, D),
    "llama_1b-8-64": (8, PAGES_PER_SLOT, 64, HQ, HKV, D),
    "pythia_cell": (32, 88, 16, 16, 16, 128),  # chipbench/workloads/pythia-1.4b.*.json: a run of 16 pages
    "olmo_cell": (48, 80, 16, 32, 32, 128),  # olmo-hybrid-7b.chat-saturated, a full-attention layer: a run of 8
    "falcon_cell": (80, 80, 16, 20, 4, 128),  # falcon-h1-34b.chat-saturated, every layer: a run of 64 pages, 5 queries a KV head
    "grouped_32_over_8": (32, 88, 16, 32, 8, 128),  # a run of 32 pages; 8 KV heads of bf16 half-fill a tile
    "pages_of_64": (8, 32, 64, 32, 8, 128),
    "pythia_tp4_shard": (32, 88, 16, 4, 4, 128),  # what `_tp_paged_attention` hands a chip of four
    "one_kv_head": (8, 32, 16, 8, 1, 128),  # staged for every pool but fp32: a packed sublane holds 2 or 4 heads
}
POOL_DTYPES = {"bf16": jnp.bfloat16, "int8": jnp.int8, "fp8": jnp.float8_e4m3fn}


def _paged_args(v5e, shape, s_block, pool):
    slots, pages_per_slot, page_size, hq, hkv, d = shape

    def spec(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=v5e)

    num_pages = slots * pages_per_slot + 1
    args = [
        spec((slots, s_block, hq, d), jnp.bfloat16),
        spec((num_pages, page_size, hkv, d), POOL_DTYPES[pool]),
        spec((num_pages, page_size, hkv, d), POOL_DTYPES[pool]),
        spec((slots, pages_per_slot), jnp.int32),
        spec((slots, s_block), jnp.int32),
    ]
    if pool != "bf16":
        args += [spec((num_pages, hkv), jnp.float32)] * 2
    return args


def _paged_fn(s_block, pool):
    from accelerate_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_verify_attention,
    )

    kernel = paged_decode_attention if s_block == 1 else paged_verify_attention
    if pool != "bf16":
        return lambda q, k, v, table, pos, k_scale, v_scale: kernel(
            q, k, v, table, pos, interpret=False, k_scale=k_scale, v_scale=v_scale)
    return lambda q, k, v, table, pos: kernel(q, k, v, table, pos, interpret=False)


@pytest.mark.parametrize("shape", list(PAGED_SHAPES))
@pytest.mark.parametrize("pool", list(POOL_DTYPES))
@pytest.mark.parametrize("s_block", [1, 5], ids=["decode", "verify5"])
def test_paged_attention_compiles_for_v5e(v5e, s_block, pool, shape):
    """`paged_decode_attention` (s=1) and `paged_verify_attention` (a speculative
    block of 5: up to 160 query rows) at llama-1b's widths, the serving cells'
    shapes, grouped-query ones and a tensor-parallel shard's, bf16 pools and
    int8 / fp8 pools with scale operands: the page copies out of an HBM pool,
    the merge of a run's (token, head) rows and the VMEM the products take are
    what interpret mode cannot see. `interpret=False` is explicit:
    `default_backend()` is cpu here."""
    from accelerate_tpu.ops.attention import kernel_refuses, kernel_stages_pool

    slots, pages_per_slot, page_size, hq, hkv, d = PAGED_SHAPES[shape]
    assert kernel_refuses(slots, pages_per_slot, page_size, s_block, hq, hkv, d, 2) is None
    compiled = _compile(_paged_fn(s_block, pool), *_paged_args(v5e, PAGED_SHAPES[shape], s_block, pool))
    pool_itemsize = jnp.dtype(POOL_DTYPES[pool]).itemsize
    if not kernel_stages_pool(hkv, d, pool_itemsize):
        # read in place: no copy of a pool is made on the way to the kernel
        pool_bytes = (slots * pages_per_slot + 1) * page_size * hkv * d * pool_itemsize
        assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 4


@pytest.mark.parametrize(
    "shape,s_block,fits",
    [
        ((112, 2048, 16, 32, 8, 128), 1, True),  # 896 KiB of page tables in SMEM
        ((128, 2048, 16, 32, 8, 128), 1, False),  # 1 MiB: the compiler refuses the operand
        ((32, 88, 16, 64, 8, 128), 18, True),  # 1,152 query rows: ~61 MB of VMEM
        ((700, 88, 16, 32, 32, 128), 5, False),  # queries and outputs alone are 57 MB
    ],
    ids=["smem_fits", "smem_over", "vmem_fits", "vmem_over"],
)
def test_paged_attention_refusal_is_the_compilers(v5e, shape, s_block, fits):
    """`ops.attention.kernel_refuses` — which keeps the engine's choice off the
    kernel, and refuses a NAMED kernel in the engine's constructor — says what
    the chip's compiler says about SMEM and VMEM, on either side of each edge."""
    from accelerate_tpu.ops.attention import kernel_refuses

    slots, pages_per_slot, page_size, hq, hkv, d = shape
    assert (kernel_refuses(slots, pages_per_slot, page_size, s_block, hq, hkv, d, 2) is None) == fits
    lowered = jax.jit(_paged_fn(s_block, "bf16")).lower(*_paged_args(v5e, shape, s_block, "bf16"))
    if fits:
        lowered.compile()
    else:
        with pytest.raises(Exception, match="smem|vmem"):
            lowered.compile()


# One layer of the benchmark's serving cells (chipbench/workloads/pythia-1.4b.*.json):
# 32 slots x 88 pages of 16 tokens, 16 heads of 128, a pool of 2,816 pages + scratch.
CELL_SLOTS, CELL_PAGES_PER_SLOT, CELL_PAGE_SIZE, CELL_HEADS, CELL_D = 32, 88, 16, 16, 128
CELL_PAGE = CELL_PAGE_SIZE * CELL_HEADS * CELL_D  # elements of one page of K
CELL_WINDOW = CELL_SLOTS * CELL_PAGES_PER_SLOT * CELL_PAGE
_HLO_RESULT = re.compile(r"^\s*(?:ROOT )?%\S+ = ([a-z]+[0-9]+\w*)\[([0-9,]+)\]\S* ([a-z\-]+)\(")


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("s_block", [1, 5], ids=["decode", "verify5"])
def test_xla_paged_read_walks_blocks_not_the_window(v5e, s_block, pool):
    """`slot_cache_attention(attention_impl="xla")` — `_write_slot_pool`'s
    scatter, then `_live_page_attention` — as the v5e's compiler leaves it:
    ONE `while` whose body gathers a BLOCK of K pages and a block of V pages
    ([128, 16, 16, 128], 8.4 MB each in bf16) and folds them into a running
    softmax. Nothing has the size of the window ([32, 88, 16, 16, 128], 185 MB
    a tensor in bf16, which the read gathered whole, K and V, until PR 28),
    the pool is copied nowhere on its way into the loop, no list of scores
    exists outside it (until PR 30 a first loop wrote one of `flat_len` pages'
    scores for a window-shaped softmax and a second loop read it back), and
    the program's temporaries are not even one block (PERF.md §6, PR 28 and
    PR 30). A compile, not a timing."""
    import flax.linen as nn

    from accelerate_tpu.ops import attention

    num_pages = CELL_SLOTS * CELL_PAGES_PER_SLOT + 1

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, q, k, v, positions, table):
            return attention.slot_cache_attention(
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
    text = compiled.as_text()
    window_pages = CELL_SLOTS * CELL_PAGES_PER_SLOT
    block_pages = attention.read_block_pages(  # 128 pages: 8 MiB of bf16
        window_pages, CELL_PAGE_SIZE, CELL_HEADS, CELL_D, 2
    )
    pool_shape = f"[{num_pages},{CELL_PAGE_SIZE},{CELL_HEADS},{CELL_D}]"

    def results(lines):
        for line in lines:
            m = _HLO_RESULT.match(line)
            if m:
                yield (line.strip(), math.prod(int(n) for n in m.group(2).split(",")),
                       f"[{m.group(2)}]" == pool_shape, m.group(3))

    window_sized, pool_copies, block_gathers = [], [], 0
    for line, elements, is_pool, op in results(text.splitlines()):
        if is_pool and op.startswith("copy"):
            pool_copies.append(line[:160])
        # The pool itself is an operand of its in-place scatter, of each block's
        # gather and of the loop's tuple; anything else of a quarter of the
        # window or more is the window coming back.
        if not is_pool and elements >= CELL_WINDOW // 4:
            window_sized.append(line[:160])
        block_gathers += elements == block_pages * CELL_PAGE and "/while/body/" in line \
            and "gather" in line
    assert not window_sized, window_sized
    assert not pool_copies, pool_copies
    assert text.count(" while(") == 1
    assert block_gathers >= 2  # a block of K and a block of V, in the one body
    # What the program materializes outside the loop (the entry computation's
    # own instructions; fused computations and the loop's body are others).
    # The list of scores was flat_len x s x Hq x page_size elements, and the
    # window-shaped scores and probabilities as many: nothing of that size is
    # left but the pool, and the pages a quantized write gathers to requantize
    # (`kv_write`: slots x s pages, more elements than a decode's scores).
    entry = text[text.index("\nENTRY "):]
    flat_len = -(-window_pages // block_pages) * block_pages
    scores = flat_len * s_block * CELL_HEADS * CELL_PAGE_SIZE
    scores_sized = [
        line[:160] for line, elements, is_pool, op in results(entry[: entry.index("\n}")].splitlines())
        if elements >= scores and not is_pool and op != "bitcast" and "/kv_write/" not in line
    ]
    assert not scores_sized, scores_sized
    # Loop-body temporaries: under two blocks of bf16 pages (PR 25's read kept
    # 184.6 MB, one window). Found: 1.5-2.2 MB — the compiler keeps both
    # gathered blocks of a turn, in the pool's dtype, in the chip's fast memory
    # (`S(1)` in their layouts), which is why a page is read from HBM once.
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * block_pages * CELL_PAGE * 2
    stored = "s8" if pool == "int8" else "bf16"
    in_fast_memory = re.findall(
        rf"= {stored}\[{block_pages},{CELL_PAGE_SIZE},{CELL_HEADS},{CELL_D}\]\{{[^}}]*S\(1\)\}} fusion\(", text
    )
    assert len(in_fast_memory) >= 2, in_fast_memory
    # `bytes accessed` counts the loop body ONCE, so it is bytes a loop turn,
    # not a dispatch. bf16 decode: 0.0753 GB with blocks of 128 pages and
    # 0.1340 GB with blocks of 256, so a turn is 0.0587 GB (the pool's pages
    # read, the gathered block written and read back, for K and for V: six
    # passes over 8.4 MB and the turn's small operands) and the rest 0.0166 GB:
    # at the 870 live pages of 2,816 the saturated cell holds, 7 turns, 0.428
    # GB a layer against the 0.586 GB of PR 28's two loops and the 1.156 GB of
    # PR 25's read. int8 decode 0.0995 GB (0.236 at PR 28), bf16 verify5 0.163
    # (0.745), int8 verify5 0.372 (0.974): the scores lists were most of those.
    bound = {("bf16", 1): 0.082e9, ("int8", 1): 0.108e9, ("bf16", 5): 0.176e9, ("int8", 5): 0.40e9}
    assert compiled.cost_analysis()["bytes accessed"] <= bound[(pool, s_block)]


# One layer of `chipbench/workloads/kimi-vl-a3b.decode-heavy-saturated.json`: 128 slots x
# 128 pages of 16 latent rows of 640 values ([c 512 | k_pe 64] in whole 128-lane tiles),
# 16 query heads in the row's space, the first 512 columns of a row its values.
LATENT_SLOTS, LATENT_PAGES_PER_SLOT, LATENT_ROW, LATENT_VALUES = 128, 128, 640, 512


@pytest.mark.parametrize("s_block", [1, 5], ids=["decode", "verify5"])
def test_latent_read_gathers_one_block_a_turn(v5e, s_block):
    """The same `_live_page_attention` over a pool of latent rows (`v=None`):
    ONE `while` whose body gathers ONE block of pages ([408, 16, 640], 8.4 MB:
    keys and values both) where two pools of full heads cost two — 51 runs of
    8 pages of one slot each (`read_run_pages`: 16 heads read the one row, so
    an entry is 128 tokens wide for the matrix unit), with `q` gathered by run
    ([51, 16, 640]; by page it would be as large as the block). The pool is
    [pages, page_size, row] with no head axis and a row of whole tiles: with a
    size-1 axis, or 576 columns, the compiler lays it out page-minor and copies
    all of it in every program that gathers pages (seen: one `copy` of the pool
    a layer, 2.7 GB a decode step). Nothing of a quarter of the window leaves
    the loop. A compile, not a timing."""
    import flax.linen as nn

    from accelerate_tpu.ops import attention

    num_pages = LATENT_SLOTS * LATENT_PAGES_PER_SLOT + 1

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, q, row, positions, table):
            return attention.slot_cache_attention(
                self, q, row, None, LATENT_PAGES_PER_SLOT * CELL_PAGE_SIZE, positions, page_table=table,
                page_size=CELL_PAGE_SIZE, num_pages=num_pages, attention_impl="xla",
                scale=1.0 / math.sqrt(192), value_dim=LATENT_VALUES,
            )

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    layer = Layer()
    operands = (spec((LATENT_SLOTS, s_block, CELL_HEADS, LATENT_ROW), jnp.bfloat16),
                spec((LATENT_SLOTS, s_block, LATENT_ROW), jnp.bfloat16),
                spec((LATENT_SLOTS, s_block), jnp.int32),
                spec((LATENT_SLOTS, LATENT_PAGES_PER_SLOT), jnp.int32))
    cache = jax.eval_shape(lambda *a: layer.init(jax.random.key(0), *a), *operands)["cache"]
    assert {name: leaf.shape for name, leaf in cache.items()} == {
        "cached_latent": (num_pages, CELL_PAGE_SIZE, LATENT_ROW)}  # one pool: no `cached_value`
    cache = jax.tree_util.tree_map(lambda leaf: spec(leaf.shape, leaf.dtype), cache)

    def step(cache, *args):
        out, mutated = layer.apply({"cache": cache}, *args, mutable=["cache"])
        return out, mutated["cache"]

    compiled = jax.jit(step, donate_argnums=0).lower(cache, *operands).compile()
    text = compiled.as_text()
    window_pages = LATENT_SLOTS * LATENT_PAGES_PER_SLOT
    block_pages = attention.read_block_pages(window_pages, CELL_PAGE_SIZE, 1, LATENT_ROW, 2)
    run_pages = attention.read_run_pages(CELL_PAGE_SIZE, CELL_HEADS)
    assert (block_pages, run_pages) == (409, 8)  # 8 MiB of bf16 rows; runs of 128 tokens
    block_pages = block_pages // run_pages * run_pages  # 51 whole runs a turn
    pool_shape = f"[{num_pages},{CELL_PAGE_SIZE},{LATENT_ROW}]"
    window = window_pages * CELL_PAGE_SIZE * LATENT_ROW
    window_sized, pool_copies = [], []
    for line in text.splitlines():
        m = _HLO_RESULT.match(line)
        if not m:
            continue
        is_pool = f"[{m.group(2)}]" == pool_shape
        if is_pool and m.group(3).startswith("copy"):
            pool_copies.append(line.strip()[:160])
        if not is_pool and math.prod(int(n) for n in m.group(2).split(",")) >= window // 4:
            window_sized.append(line.strip()[:160])
    assert not pool_copies, pool_copies
    assert not window_sized, window_sized
    assert text.count(" while(") == 1
    # the pool's parameter is row-major: [pages, page_size, row] as it is indexed
    assert re.search(rf"= bf16\[{num_pages},{CELL_PAGE_SIZE},{LATENT_ROW}\]\{{2,1,0:[^}}]*\}} parameter\(", text)
    # In fast memory (`S(1)`), a turn: the ONE block of pages.
    blocks = re.findall(rf"= bf16\[{block_pages},{CELL_PAGE_SIZE},{LATENT_ROW}\]\{{[^}}]*S\(1\)\}} fusion\(", text)
    assert len(blocks) == 1, blocks
    assert compiled.memory_analysis().temp_size_in_bytes <= 4 * block_pages * CELL_PAGE_SIZE * LATENT_ROW * 2


def _latent_paged(v5e, slots, pages_per_slot, s_block, page_size=CELL_PAGE_SIZE, row=LATENT_ROW):
    """The page-walk kernel over ONE pool of latent rows, lowered for the
    described chip: (lowered, why `kernel_refuses` would refuse the shape)."""
    from accelerate_tpu.ops.attention import kernel_refuses
    from accelerate_tpu.ops.paged_attention import paged_verify_attention

    def read(q, pool, table, pos):
        return paged_verify_attention(q, pool, None, table, pos, scale=1.0 / math.sqrt(192),
                                      value_dim=LATENT_VALUES, interpret=False)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    lowered = jax.jit(read).lower(
        spec((slots, s_block, CELL_HEADS, row), jnp.bfloat16),
        spec((slots * pages_per_slot + 1, page_size, row), jnp.bfloat16),
        spec((slots, pages_per_slot), jnp.int32), spec((slots, s_block), jnp.int32))
    return lowered, kernel_refuses(slots, pages_per_slot, page_size, s_block, CELL_HEADS, 1, row, 2, latent=True)


@pytest.mark.parametrize("s_block", [1, 5], ids=["decode", "verify5"])
def test_latent_paged_attention_compiles_for_v5e(v5e, s_block):
    """The kimi cell's read as the engine chooses it on a TPU since PR 39: the
    page-walk kernel over the one pool of latent rows, 128 slots x 128 pages of
    [16, 640] bf16 (five whole tiles a page, cut out of the pool in place),
    runs of 96 pages (2 MiB: a K run and a V run) in pieces of 256 tokens, the
    values a row's first 512 columns. One Mosaic kernel and NO copy of the pool on the way in: the
    program's temporaries are a few KB where the XLA read keeps an 8 MB block."""
    from accelerate_tpu.ops import attention

    assert attention.kernel_run_pages(LATENT_PAGES_PER_SLOT, CELL_PAGE_SIZE, 1, LATENT_ROW, 2, latent=True) == 96
    assert attention.kernel_refuses_rows(CELL_PAGE_SIZE, LATENT_ROW, 2) is None
    lowered, refused = _latent_paged(v5e, LATENT_SLOTS, LATENT_PAGES_PER_SLOT, s_block)
    assert refused is None
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 1 and " while(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize(
    "slots,pages_per_slot,s_block,fits",
    [
        (112, 2048, 1, True),  # 896 KiB of page tables in SMEM
        (128, 2048, 1, False),  # 1 MiB: the compiler refuses the operand
        (280, 128, 5, True),  # 22,400 query rows of 640 and their outputs, ONE pool's two run buffers: ~60 MB of VMEM
        (400, 128, 5, False),  # queries and outputs alone are 74 MB
    ],
    ids=["smem_fits", "smem_over", "vmem_fits", "vmem_over"],
)
def test_latent_paged_attention_refusal_is_the_compilers(v5e, slots, pages_per_slot, s_block, fits):
    """`kernel_refuses(..., latent=True)` — one pool's buffers, nothing staged
    or widened — against the chip's compiler on either side of each edge."""
    lowered, refused = _latent_paged(v5e, slots, pages_per_slot, s_block)
    assert (refused is None) == fits, refused
    if fits:
        lowered.compile()
    else:
        with pytest.raises(Exception, match="smem|vmem"):
            lowered.compile()


@pytest.mark.parametrize("page_size,row", [(8, 640), (16, 576)], ids=["half_a_tile_of_rows", "row_of_576"])
def test_latent_pool_not_of_whole_tiles_is_refused_before_the_compiler(v5e, page_size, row):
    """What the rule keeps on the XLA read and a named kernel is refused for:
    the read itself raises by name, and never pads the pool."""
    with pytest.raises(ValueError, match="latent rows are never staged"):
        _latent_paged(v5e, 8, 32, 1, page_size=page_size, row=row)


@pytest.mark.parametrize("rows", [768, 192, 6144], ids=["decode128x6", "insert32x6", "insert1024x6"])
@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)], ids=["gate_up", "down"])
def test_grouped_expert_matmul_compiles_for_v5e(v5e, rows, k, n):
    """`parallel.expert._gmm`, the Pallas grouped matmul the routed experts run
    on a TPU, at the `kimi-vl-a3b` cell's shapes: 64 experts, a decode step's
    768 (token, expert) pairs and the smallest and largest insert buckets'
    (192 is padded to whole tiles of 128 rows). One kernel, no copy of the
    stacked matrices on its way in."""
    from accelerate_tpu.parallel.expert import _gmm

    compiled = jax.jit(_gmm).lower(
        jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=v5e),
        jax.ShapeDtypeStruct((64, k, n), jnp.bfloat16, sharding=v5e),
        jax.ShapeDtypeStruct((64,), jnp.int32, sharding=v5e),
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%gmm[.\d]* = [^\n]*custom-call\(", text)) == 1
    assert not re.search(rf"= bf16\[64,{k},{n}\]\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20


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


# The hybrid linear-attention cell's shapes (`olmo-hybrid-7b.chat-saturated`):
# 48 slots, 30 heads of a 96 x 192 float32 state, 11,520 convolution channels.
HYBRID_SLOTS, HYBRID_HEADS, HYBRID_DK, HYBRID_DV = 48, 30, 96, 192
HYBRID_STATE_BYTES = HYBRID_SLOTS * HYBRID_HEADS * HYBRID_DK * HYBRID_DV * 4


@pytest.mark.parametrize("column_block", [None, 1920], ids=["whole_row", "blocks_of_10_heads"])
def test_delta_step_kernel_keeps_the_state_at_its_bytes(v5e, column_block):
    """`ops.delta_rule`'s one-token update at the cell's shapes: ONE Mosaic
    kernel, the state `[48, 96, 5760]` float32 held at its values' bytes (a
    `[.., 96, 192]` matrix a head would be laid out as `[.., 96, 256]`, a third
    more), updated in place, nothing of its size made beside it."""
    from accelerate_tpu.ops.delta_rule import _delta_step_pallas

    def step(state, q, k, v, alpha, beta):
        return _delta_step_pallas(state, q, k, v, alpha, beta, interpret=False, column_block=column_block)

    def operand(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    heads = (HYBRID_SLOTS, HYBRID_HEADS)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        operand((HYBRID_SLOTS, HYBRID_DK, HYBRID_HEADS * HYBRID_DV), jnp.float32),
        operand(heads + (HYBRID_DK,), jnp.bfloat16), operand(heads + (HYBRID_DK,), jnp.bfloat16),
        operand(heads + (HYBRID_DV,), jnp.bfloat16), operand(heads, jnp.float32), operand(heads, jnp.float32),
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%delta_step[.\d]* = [^\n]*custom-call\(", text)) == 1
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes < HYBRID_STATE_BYTES * 1.02  # no padded layout: 106.2 MB, not 141.6
    assert memory.alias_size_in_bytes >= HYBRID_STATE_BYTES  # in place
    assert memory.temp_size_in_bytes < 4 << 20


def test_hybrid_linear_layer_decodes_without_a_copy_of_its_state(v5e, monkeypatch):
    """One linear-attention layer's decode step as the engine's chunk holds it
    (`models.olmo_hybrid.GatedDeltaNet` against by-slot leaves for 48 slots):
    the kernel is in the program, and no operation makes, copies or re-lays-out
    an array of the state's size."""
    import dataclasses

    from accelerate_tpu.models.olmo_hybrid import GatedDeltaNet, OlmoHybridConfig

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the layer picks its kernel by the backend
    cfg = dataclasses.replace(
        OlmoHybridConfig(num_hidden_layers=4, param_dtype="bfloat16"), decode_cache_length=1280,
        decode_slot_cache=True, decode_page_size=16, decode_num_pages=3841)
    layer = GatedDeltaNet(cfg)
    hidden = jax.ShapeDtypeStruct((HYBRID_SLOTS, 1, cfg.hidden_size), jnp.bfloat16, sharding=v5e)
    variables = jax.eval_shape(lambda h: layer.init(jax.random.key(0), h, None), hidden)
    described = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=v5e), variables)
    assert described["cache"]["recurrent_state"].shape == (HYBRID_SLOTS, HYBRID_DK, HYBRID_HEADS * HYBRID_DV)
    assert described["cache"]["conv_state"].shape == (HYBRID_SLOTS, 3, cfg.linear_conv_channels)

    def decode(params, cache, h):
        return layer.apply({"params": params, "cache": cache}, h, None, mutable=["cache"])

    compiled = jax.jit(decode, donate_argnums=(1,)).lower(
        described["params"], described["cache"], hidden).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%delta_step[.\d]* = [^\n]*custom-call\(", text)) == 1
    state_shaped = re.findall(r"= f32\[48,(?:96,5760|30,96,192|96,30,192)\]\S* (\w+)\(", text)
    assert set(state_shaped) <= {"custom-call", "parameter", "get-tuple-element", "bitcast"}, state_shaped
    assert compiled.memory_analysis().temp_size_in_bytes < HYBRID_STATE_BYTES // 4


@pytest.mark.parametrize("tokens", [128, 512])
def test_hybrid_linear_layer_prefill_compiles_for_v5e(v5e, tokens):
    """The chunked scan over an insert bucket (batch 1, chunks of 64, a
    triangular solve a chunk at float32) compiles for the chip and stays small:
    the program around it holds 13.5 GB of weights, pool and state."""
    import dataclasses

    from accelerate_tpu.models.olmo_hybrid import GatedDeltaNet, OlmoHybridConfig

    cfg = dataclasses.replace(OlmoHybridConfig(num_hidden_layers=4, param_dtype="bfloat16"), decode_cache_length=1280)
    layer = GatedDeltaNet(cfg)
    hidden = jax.ShapeDtypeStruct((1, tokens, cfg.hidden_size), jnp.bfloat16, sharding=v5e)
    real = jax.ShapeDtypeStruct((1, tokens), jnp.bool_, sharding=v5e)
    variables = jax.eval_shape(lambda h, m: layer.init(jax.random.key(0), h, m), hidden, real)
    described = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=v5e), variables)

    def prefill(params, cache, h, m):
        return layer.apply({"params": params, "cache": cache}, h, m, mutable=["cache"])

    compiled = jax.jit(prefill).lower(described["params"], described["cache"], hidden, real).compile()
    assert "tpu_custom_call" not in compiled.as_text()  # the scan is XLA's; the kernel is the decode step's
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


# The parallel state-space cell's shapes (`falcon-h1-34b.chat-saturated`): 80
# slots, 32 heads of a 128 x 256 float32 state in 2 groups, 4 KV heads of 128.
SSM_SLOTS, SSM_HEADS, SSM_P, SSM_GROUPS, SSM_N = 80, 32, 128, 2, 256
SSM_STATE_BYTES = SSM_SLOTS * SSM_HEADS * SSM_P * SSM_N * 4


def test_ssm_step_kernel_keeps_the_state_at_its_bytes(v5e):
    """`ops.ssm`'s one-token update at the cell's shapes: ONE Mosaic kernel, the
    state `[80, 256, 4096]` float32 held at its values' bytes, updated in
    place, nothing of its size made beside it (written head-major in
    `jax.numpy` the step made two broadcasts of the state's size a layer)."""
    from accelerate_tpu.ops.ssm import _ssm_step_pallas

    def step(state, x, dt, a, b_in, c_in):
        return _ssm_step_pallas(state, x, dt, a, b_in, c_in, interpret=False)

    def operand(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        operand((SSM_SLOTS, SSM_N, SSM_HEADS * SSM_P), jnp.float32),
        operand((SSM_SLOTS, SSM_HEADS, SSM_P), jnp.bfloat16), operand((SSM_SLOTS, SSM_HEADS), jnp.float32),
        operand((SSM_HEADS,), jnp.float32), operand((SSM_SLOTS, SSM_GROUPS, SSM_N), jnp.bfloat16),
        operand((SSM_SLOTS, SSM_GROUPS, SSM_N), jnp.bfloat16),
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%ssm_step[.\d]* = [^\n]*custom-call\(", text)) == 1
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes < SSM_STATE_BYTES * 1.02  # no padded layout: 335.5 MB
    assert memory.alias_size_in_bytes >= SSM_STATE_BYTES  # in place
    assert memory.temp_size_in_bytes < 8 << 20


def _falcon_layer(**decode):
    import dataclasses

    from accelerate_tpu.models.falcon_h1 import FalconH1Config, FalconH1Layer

    cfg = dataclasses.replace(FalconH1Config(num_hidden_layers=6, param_dtype="bfloat16"), **decode)
    return cfg, FalconH1Layer(cfg)


def test_parallel_hybrid_layer_decodes_with_both_kinds_of_leaf_at_their_bytes(v5e, monkeypatch):
    """One block's decode step as the engine's chunk holds it
    (`models.falcon_h1.FalconH1Layer` against 80 slots): its cache holds page
    leaves AND by-slot leaves, the state's kernel and the page-walk kernel are
    both in the program, and no operation makes, copies or re-lays-out an array
    of the state's size or of a pool's."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the layer picks its kernel by the backend
    pages = SSM_SLOTS * 80 + 1
    cfg, layer = _falcon_layer(decode_cache_length=1280, decode_slot_cache=True, decode_page_size=16,
                               decode_num_pages=pages, decode_attention_impl="pallas_paged")
    hidden = jax.ShapeDtypeStruct((SSM_SLOTS, 1, cfg.hidden_size), jnp.bfloat16, sharding=v5e)
    positions = jax.ShapeDtypeStruct((SSM_SLOTS, 1), jnp.int32, sharding=v5e)
    table = jax.ShapeDtypeStruct((SSM_SLOTS, 80), jnp.int32, sharding=v5e)
    variables = jax.eval_shape(lambda h, p, t: layer.init(jax.random.key(0), h, p, t), hidden, positions, table)
    described = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=v5e), variables)
    cache = described["cache"]
    assert cache["mixer"]["recurrent_state"].shape == (SSM_SLOTS, SSM_N, SSM_HEADS * SSM_P)
    assert cache["mixer"]["conv_state"].shape == (SSM_SLOTS, 3, cfg.conv_channels)
    assert cache["attention"]["cached_key"].shape == (pages, 16, 4, 128)

    def decode(params, cache, h, p, t):
        return layer.apply({"params": params, "cache": cache}, h, p, t, mutable=["cache"])

    compiled = jax.jit(decode, donate_argnums=(1,)).lower(
        described["params"], cache, hidden, positions, table).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%ssm_step[.\d]* = [^\n]*custom-call\(", text)) == 1
    assert text.count("tpu_custom_call") >= 2  # the state's kernel and the page walk
    state_shaped = re.findall(r"= f32\[80,(?:256,4096|32,128,256|4096,256)\]\S* (\w+)\(", text)
    assert set(state_shaped) <= {"custom-call", "parameter", "get-tuple-element", "bitcast"}, state_shaped
    pool_shaped = re.findall(rf"= bf16\[{pages},16,4,128\]\S* (\w+)\(", text)
    assert "copy" not in pool_shaped and "transpose" not in pool_shaped, pool_shaped
    memory = compiled.memory_analysis()
    stored = SSM_STATE_BYTES + SSM_SLOTS * 3 * cfg.conv_channels * 2 + 2 * pages * 16 * 4 * 128 * 2
    weights = sum(math.prod(s.shape) * s.dtype.itemsize for s in jax.tree_util.tree_leaves(described["params"]))
    assert memory.argument_size_in_bytes < (stored + weights) * 1.02  # every leaf at its bytes
    assert memory.temp_size_in_bytes < SSM_STATE_BYTES // 4


@pytest.mark.parametrize("tokens", [128, 512])
def test_parallel_hybrid_layer_prefill_compiles_for_v5e(v5e, tokens):
    """The chunked scan over an insert bucket (batch 1, chunks of 128, float32)
    beside the attention half's dense prefill compiles for the chip and stays
    small: the program around it holds 13.8 GB of weights, pool and state."""
    cfg, layer = _falcon_layer(decode_cache_length=1280)
    hidden = jax.ShapeDtypeStruct((1, tokens, cfg.hidden_size), jnp.bfloat16, sharding=v5e)
    positions = jax.ShapeDtypeStruct((1, tokens), jnp.int32, sharding=v5e)
    real = jax.ShapeDtypeStruct((1, tokens), jnp.bool_, sharding=v5e)
    variables = jax.eval_shape(lambda h, p, m: layer.init(jax.random.key(0), h, p, m), hidden, positions, real)
    described = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=v5e), variables)

    def prefill(params, cache, h, p, m):
        return layer.apply({"params": params, "cache": cache}, h, p, m, mutable=["cache"])

    compiled = jax.jit(prefill).lower(described["params"], described["cache"], hidden, positions, real).compile()
    assert "ssm_step" not in compiled.as_text()  # the scan is XLA's; the kernel is the decode step's
    assert compiled.memory_analysis().temp_size_in_bytes < 512 << 20


# ------------------------------------------------- the residual streams' mixes (PR 40)
HC_STREAMS, HC_HIDDEN = 4, 3584  # xing4-29b-a4b: four streams of 3,584, one row of 14,336 a token


@pytest.mark.parametrize("rows", [2048, 256, 64, 200], ids=["insert2048", "insert256", "chunk64", "ragged200"])
def test_hyper_connection_kernels_compile_for_v5e(v5e, rows):
    """`hc_pre` and `hc_post` at the prompt-heavy cell's shapes (bfloat16
    streams, float32 maps), a row count short of one block and one that is no
    whole number of blocks: Mosaic takes the 24-row products, the whole-tile
    transposes and the single-row stores, in 64 MB of fast memory; each kernel
    is one custom call, and the streams are stored at their bytes (no `[rows,
    4, 3584]` array with 4 on a tiled axis anywhere)."""
    from accelerate_tpu.ops import hyper_connection as hc

    width = HC_STREAMS * HC_HIDDEN
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=v5e)  # noqa: E731
    x, y = shape((rows, width), jnp.bfloat16), shape((rows, HC_HIDDEN), jnp.bfloat16)
    phi_t, alpha, bias = shape((24, width), jnp.float32), shape((3,), jnp.float32), shape((24,), jnp.float32)

    def mix(x, y, phi_t, alpha, bias):
        u, maps = hc.hc_pre(x, phi_t, alpha, bias, n=HC_STREAMS, iters=20, eps=1e-6, impl="pallas")
        return hc.hc_post(x, y + u, maps, n=HC_STREAMS, impl="pallas"), maps

    # off a TPU "pallas" means the interpreter: steer the two wrappers to the compiler as the chip would
    import unittest.mock

    with unittest.mock.patch.object(jax, "default_backend", lambda: "tpu"):
        compiled = _compile(mix, x, y, phi_t, alpha, bias)
    text = compiled.as_text()
    assert len(re.findall(r"%hc_pre[.\d]* = [^\n]*custom-call\(", text)) == 1
    assert len(re.findall(r"%hc_post[.\d]* = [^\n]*custom-call\(", text)) == 1
    assert not re.findall(rf"\[{rows},{HC_STREAMS},{HC_HIDDEN}\]", text)
    memory = compiled.memory_analysis()
    streams = rows * width * 2
    stored = streams + rows * hc.MAP_LANES * 4  # X' and a packed row of maps a token, and the pair's table
    assert stored <= memory.output_size_in_bytes <= stored + 4096
    assert memory.temp_size_in_bytes <= rows * (HC_HIDDEN * 2 * 2 + hc.MAP_LANES * 4) + (1 << 20)  # u and y + u


# ------------------------------------- the latent prefill's attention at the causal frontier (PR 43)
# `xing4-29b-a4b.prompt-heavy-saturated`: 32 heads, a slot's window of 136 pages of 16, keys of 128 + the 64
# rotary columns all heads share, values of 128; an insert's suffix buckets.
FRONTIER_HEADS, FRONTIER_WINDOW = 32, 2176
FRONTIER_KEY, FRONTIER_SHARED, FRONTIER_VALUE = 128, 64, 128


@pytest.mark.parametrize("rows", [256, 512, 1024, 2048])
def test_frontier_attention_compiles_for_v5e(v5e, rows):
    """`ops.frontier_attention` at the prompt-heavy cell's four insert shapes,
    at its shipped block sizes: ONE Mosaic call whose output is already
    `[rows, heads * 128]` (what `wo` multiplies), no `[32, rows, 2176]` value
    and no temporary beside it — the head's whole window of keys and values
    sits in the kernel's VMEM, under its stated limit."""
    from accelerate_tpu.ops import frontier_attention as frontier

    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=v5e)  # noqa: E731
    q = shape(1, FRONTIER_HEADS, FRONTIER_KEY + FRONTIER_SHARED, rows)  # queries and values come transposed
    k = shape(1, FRONTIER_HEADS, FRONTIER_WINDOW, FRONTIER_KEY)
    v = shape(1, FRONTIER_HEADS, FRONTIER_VALUE, FRONTIER_WINDOW)
    shared = shape(1, FRONTIER_WINDOW, FRONTIER_SHARED)
    cur = jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e)

    def attend(q, k, v, shared, cur):
        return frontier.frontier_attention(q, k, v, cur, scale=0.07, shared_k=shared, interpret=False)

    compiled = _compile(attend, q, k, v, shared, cur)
    text = compiled.as_text()
    assert len(re.findall(r"%frontier_attention[.\d]* = [^\n]*custom-call\(", text)) == 1
    assert not re.search(rf"\[(1,)?{FRONTIER_HEADS},{rows},{FRONTIER_WINDOW}\]", text)
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == rows * FRONTIER_HEADS * FRONTIER_VALUE * 2
    assert memory.temp_size_in_bytes < 1 << 20


def _tiny_latent_engine(hc: bool):
    """An engine of the tiny latent preset at the CELL's attention shapes (32
    heads of 128 + 64 and 128, a window of 2,176) and nothing else of its size."""
    import dataclasses

    from accelerate_tpu.models import latent_moe
    from accelerate_tpu.serving import ContinuousBatcher

    tiny = latent_moe.latent_moe_hc_tiny() if hc else latent_moe.latent_moe_tiny()
    config = dataclasses.replace(
        tiny, num_hidden_layers=2, first_k_dense_replace=1, num_attention_heads=FRONTIER_HEADS,
        qk_nope_head_dim=FRONTIER_KEY, qk_rope_head_dim=FRONTIER_SHARED, v_head_dim=FRONTIER_VALUE,
        max_position_embeddings=4096, param_dtype="bfloat16")
    model = latent_moe.create_latent_moe_model(config, jax.random.key(0))
    return ContinuousBatcher(model, num_slots=2, max_length=FRONTIER_WINDOW, chunk_size=4, page_size=16)


@pytest.mark.parametrize("rows", [256, 512, 1024, 2048])
def test_the_latent_insert_compiled_for_v5e_holds_no_scores_of_the_window(v5e, rows, monkeypatch):
    """An engine's insert of the four-stream latent family at the cell's
    attention shapes, compiled for the described v5e as the chip would trace
    it (`jax.default_backend` is "tpu" there): a `frontier_attention` call a
    layer and NO value of shape `[32, rows, 2176]`, lowered or compiled. The
    same insert traced as the CPU traces it holds one: the pattern can see."""
    held = rf"\[(1,)?{FRONTIER_HEADS},{rows},{FRONTIER_WINDOW}\]"
    lowered_held = f"x{FRONTIER_HEADS}x{rows}x{FRONTIER_WINDOW}x"

    def insert(engine):
        args = (engine.params, engine._cache, engine._presence, jnp.zeros((1, rows), jnp.int32),
                jnp.int32(1), jnp.int32(0), jnp.int32(0), jnp.zeros((engine.pages_per_slot,), jnp.int32),
                jnp.int32(0), jnp.float32(1), jnp.float32(1), engine._rng, engine._new_first_token())
        described = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e), args)
        return engine._insert_fn(rows).lower(*described)

    if rows == 1024:  # once: the masked XLA product over the window, as every insert was before PR 43
        before = insert(_tiny_latent_engine(hc=True))
        assert lowered_held in before.as_text() and "frontier_attention" not in before.as_text()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    engine = _tiny_latent_engine(hc=True)
    assert engine._padded_length == FRONTIER_WINDOW
    lowered = insert(engine)
    assert lowered_held not in lowered.as_text()
    text = lowered.compile().as_text()
    assert len(re.findall(r"%frontier_attention[.\d]* = [^\n]*custom-call\(", text)) == 2  # a layer
    assert not re.search(held, text)
