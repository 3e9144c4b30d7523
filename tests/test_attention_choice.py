"""Which paged read an engine takes (`ops.attention.slot_attention_impl`), and
that the choice changes nothing where it stays on the XLA read: an engine that
names `"xla"` and one that names nothing lower the same decode chunk on the CPU,
neither reaches `ops/paged_attention`, and both say so (`stats`, the chunk span)."""

import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu.ops import attention  # noqa: E402
from accelerate_tpu.serving import ContinuousBatcher, Request  # noqa: E402
from accelerate_tpu.telemetry.flight_recorder import FlightRecorder  # noqa: E402
from accelerate_tpu.telemetry.tracing import Tracer  # noqa: E402

#: pythia-1.4b's saturated cell: 32 slots x 88 pages of 16, 16 heads of 128, bf16 pools.
FULL_HEADS = dict(latent=False, tp=1, slots=32, pages_per_slot=88, page_size=16, block=1, heads=16,
                  kv_heads=16, head_dim=128, itemsize=2, kv_cache_dtype="bf16")
#: kimi-vl-a3b's saturated cell: 128 slots x 128 pages of 16 latent rows of 640 values (576 in whole lane tiles), 16 heads.
LATENT_ROW = dict(latent=True, slots=128, pages_per_slot=128, heads=16, kv_heads=1, head_dim=640)


@pytest.mark.parametrize(
    "named,observed,want",
    [
        # the engine's choice: the kernel on one TPU device, for K/V pools of full heads it reads in place
        (None, dict(platform="tpu"), "pallas_paged"),
        (None, dict(platform="tpu", heads=32, kv_heads=32), "pallas_paged"),
        (None, dict(platform="tpu", heads=32, kv_heads=8), "pallas_paged"),
        (None, dict(platform="tpu", heads=28, kv_heads=4, kv_cache_dtype="int8"), "pallas_paged"),
        # falcon-h1-34b's cell: 80 slots x 80 pages, 20 query heads over 4 KV heads of 128 (timed: PERF.md section 6, PR 38)
        (None, dict(platform="tpu", slots=80, pages_per_slot=80, heads=20, kv_heads=4), "pallas_paged"),
        (None, dict(platform="tpu", block=5), "pallas_paged"),  # a speculative engine's verify block
        (None, dict(platform="cpu"), "xla"),
        (None, dict(platform="gpu"), "xla"),
        (None, dict(platform="tpu", tp=4, heads=4, kv_heads=4), "xla"),
        # ONE pool of latent rows (MLA): the kernel where it reads the pool in place (timed: PERF.md section 6, PR 39)
        (None, dict(platform="tpu", **LATENT_ROW), "pallas_paged"),
        (None, dict(platform="tpu", **LATENT_ROW, block=5), "pallas_paged"),
        (None, dict(platform="cpu", **LATENT_ROW), "xla"),
        (None, dict(platform="tpu", **LATENT_ROW, tp=4), "xla"),
        # latent rows are never staged: a row that is not whole lanes, pages that are not whole packed sublanes
        (None, dict(platform="tpu", **{**LATENT_ROW, "head_dim": 576}), "xla"),
        (None, dict(platform="tpu", **LATENT_ROW, page_size=8), "xla"),
        (None, dict(platform="tpu", **LATENT_ROW, kv_cache_dtype="int8"), "xla"),  # no quantized latent pool is built
        (None, dict(platform="tpu", **{**LATENT_ROW, "pages_per_slot": 2048}), "xla"),  # SMEM
        (None, dict(platform="tpu", **{**LATENT_ROW, "slots": 4096, "pages_per_slot": 32}, block=5), "xla"),  # VMEM
        # a pool the kernel would have to stage stays on the XLA read
        (None, dict(platform="tpu", heads=32, kv_heads=8, head_dim=64), "xla"),  # half a lane row
        (None, dict(platform="tpu", heads=8, kv_heads=1), "xla"),  # one head of bf16: half a packed sublane
        (None, dict(platform="tpu", heads=8, kv_heads=2, kv_cache_dtype="int8"), "xla"),  # two of int8: half
        # an fp8 pool: a v5e widens it in software, 527 -> 1,158 us a layer (PERF.md section 6, PR 37)
        (None, dict(platform="tpu", kv_cache_dtype="fp8_e4m3"), "xla"),
        # and so do shapes the compiler would refuse: all slots' page tables ride SMEM, their queries VMEM
        (None, dict(platform="tpu", slots=128, pages_per_slot=2048), "xla"),
        (None, dict(platform="tpu", slots=1024, block=5, heads=32, kv_heads=32), "xla"),
        # a named read is that read, whatever is observed
        ("xla", dict(platform="tpu"), "xla"),
        ("xla", dict(platform="tpu", slots=128, pages_per_slot=2048), "xla"),
        ("xla", dict(platform="cpu", **LATENT_ROW), "xla"),
        ("xla", dict(platform="tpu", **{**LATENT_ROW, "head_dim": 576}), "xla"),
        ("pallas_paged", dict(platform="tpu", **LATENT_ROW), "pallas_paged"),
        ("pallas_paged", dict(platform="cpu", **LATENT_ROW, page_size=8, itemsize=4), "pallas_paged"),  # fp32: 8 rows a tile
        ("pallas_paged", dict(platform="cpu"), "pallas_paged"),
        ("pallas_paged", dict(platform="tpu", tp=4, heads=4, kv_heads=4), "pallas_paged"),
        ("pallas_paged", dict(platform="tpu", heads=32, kv_heads=8, head_dim=64), "pallas_paged"),  # staged
        ("pallas_paged", dict(platform="cpu", slots=128, pages_per_slot=2048), "pallas_paged"),  # interpreted
        ("pallas_paged", dict(platform="tpu", kv_cache_dtype="fp8_e4m3"), "pallas_paged"),
    ],
)
def test_the_engines_choice_of_read_is_a_table(named, observed, want):
    assert attention.slot_attention_impl(named, **{**FULL_HEADS, **observed}) == want


@pytest.mark.parametrize(
    "named,observed,says",
    [
        ("mosaic", dict(platform="tpu"), "attention_impl 'mosaic'"),
        ("pallas_paged", dict(platform="cpu", **{**LATENT_ROW, "head_dim": 576}), "latent rows are never staged"),
        ("pallas_paged", dict(platform="tpu", **LATENT_ROW, page_size=8), "pages of 8 rows of 640 values.*never staged"),
        ("pallas_paged", dict(platform="tpu", **{**LATENT_ROW, "pages_per_slot": 2048}), "bytes of SMEM"),
        ("pallas_paged", dict(platform="tpu", **{**LATENT_ROW, "slots": 4096, "pages_per_slot": 32}, block=5),
         "bytes of VMEM"),
        ("pallas_paged", dict(platform="tpu", slots=128, pages_per_slot=2048), "bytes of SMEM"),
        ("pallas_paged", dict(platform="tpu", slots=1024, block=5, heads=32, kv_heads=32), "bytes of VMEM"),
    ],
)
def test_a_named_read_the_kernel_cannot_serve_is_refused_by_name(named, observed, says):
    """Where the engine is built, not inside the first step that traces the chunk."""
    with pytest.raises(ValueError, match=says):
        attention.slot_attention_impl(named, **{**FULL_HEADS, **observed})


def test_the_constructor_refuses_a_named_kernel_the_chip_cannot_compile(monkeypatch):
    """A named `"pallas_paged"` over shapes the chip's compiler would refuse fails in
    `ContinuousBatcher.__init__` (no request is in flight yet), with the reason."""
    model, _ = _neox()
    monkeypatch.setattr(attention, "_KERNEL_SMEM_BYTES", 32)  # a chip with room for 7 scalars
    kwargs = dict(num_slots=2, max_length=32, chunk_size=4, page_size=8)
    choose = attention.slot_attention_impl  # as the engine on a TPU would call it
    monkeypatch.setattr(attention, "slot_attention_impl",
                        lambda named, **observed: choose(named, **{**observed, "platform": "tpu"}))
    with pytest.raises(ValueError, match="attention_impl='pallas_paged'.*bytes of SMEM"):
        ContinuousBatcher(model, attention_impl="pallas_paged", **kwargs)
    assert ContinuousBatcher(model, **kwargs).attention_impl == "xla"  # the choice steps aside instead


def _neox():
    from accelerate_tpu.models.gpt_neox import GPTNeoXConfig, create_gpt_neox_model

    cfg = GPTNeoXConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
        num_attention_heads=4, rotary_pct=0.5, max_position_embeddings=64,
    )
    return create_gpt_neox_model(cfg, seq_len=16), 128


def _olmo_hybrid():
    from chipbench import harness
    from test_olmo_hybrid import TINY

    params = harness.load_module("reference", "olmo_hybrid").init_params(TINY, jax.random.key(11), "float32")
    return harness.load_module("adapters", "olmo_hybrid").build_model(TINY, params, "float32"), TINY["vocab_size"]


def _falcon_h1():
    from chipbench import harness
    from test_falcon_h1 import TINY

    params = harness.load_module("reference", "falcon_h1").init_params(TINY, jax.random.key(11), "float32")
    return harness.load_module("adapters", "falcon_h1").build_model(TINY, params, "float32"), TINY["vocab_size"]


@pytest.mark.parametrize("family", [_neox, _olmo_hybrid, _falcon_h1], ids=["gpt_neox", "olmo_hybrid", "falcon_h1"])
def test_an_engine_that_names_no_read_is_the_xla_engine_on_the_cpu(family, monkeypatch):
    """The bypass: off the TPU the engine's choice is the XLA read, so the
    decode chunk of an engine built with no `attention_impl` is, as text, the
    chunk of one built with `"xla"`; `ops/paged_attention` is never entered;
    and both engines say which read they hold."""
    from accelerate_tpu.ops import paged_attention

    def never(*args, **kwargs):
        raise AssertionError("the page-walk kernel was reached")

    monkeypatch.setattr(paged_attention, "_paged_call", never)
    model, vocab = family()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, vocab, (n,)).astype(np.int32) for n in (5, 11, 8)]
    lowered, tokens = {}, {}
    for named in ("xla", None):
        recorder = FlightRecorder()
        kwargs = {} if named is None else {"attention_impl": named}
        engine = ContinuousBatcher(
            model, num_slots=2, max_length=32, chunk_size=4, page_size=8,
            tracer=Tracer(recorder=recorder, category="serve"), **kwargs,
        )
        assert engine.attention_impl == "xla" and engine.stats["attention_impl"] == "xla"
        attention.LAST_DISPATCH = None
        lowered[named] = engine.lower_decode_chunk().as_text()
        assert attention.LAST_DISPATCH == "xla"
        out = engine.run([Request(i, p, max_new_tokens=6) for i, p in enumerate(prompts)])
        tokens[named] = {rid: list(map(int, toks)) for rid, toks in out.items()}
        chunks = [r["attrs"] for r in recorder.records() if r["name"] == "serve.decode_chunk"]
        assert chunks and all(c["read_impl"] == "xla" for c in chunks)
    assert lowered[None] == lowered["xla"]
    assert tokens[None] == tokens["xla"]
