"""An insert computes the head for the ONE row it samples (PR 41): every family the
engine admits takes `logits_at` — a `[B]` index into the block — and applies its
tail (the streams' sum, the final norm, the head, a logit multiplier) to that row
alone (`models.llama.rows_for_head`). Held here, on the CPU at tiny sizes: the row is
the full call's row; an engine serves the tokens it served when its inserts
computed every row; no insert program holds a `[bucket, V]` value; the engine
refuses a module that cannot take the index; the span says `head_rows` 1; and
`generate()`'s prefill no longer computes `[B, S, V]` either."""

import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu import models  # noqa: E402
from accelerate_tpu.generation import Generator, _operand  # noqa: E402
from accelerate_tpu.modeling import Model  # noqa: E402
from accelerate_tpu.serving import ContinuousBatcher, Request  # noqa: E402
from accelerate_tpu.telemetry import FlightRecorder, Tracer  # noqa: E402

#: the families whose config has `decode_page_size` (`latent_moe.py` serves two): builder, tiny preset
ADMITTED = {
    "gpt_neox": (models.create_gpt_neox_model, models.gpt_neox_tiny),
    "llama": (models.create_llama_model, models.llama_tiny),
    "latent_moe": (models.create_latent_moe_model, models.latent_moe_tiny),
    "latent_moe_hc": (models.create_latent_moe_model, models.latent_moe_hc_tiny),  # four residual streams
    "olmo_hybrid": (models.create_olmo_hybrid_model, models.olmo_hybrid_tiny),
    "falcon_h1": (models.create_falcon_h1_model, models.falcon_h1_tiny),  # `lm_head_multiplier` 1/128
}
#: and the other decode-cache families `generate()` drives through the same prefill
GENERATED = {
    "gptj": (models.create_gptj_model, models.gptj_tiny),
    "opt": (models.create_opt_model, models.opt_tiny),
    "mixtral": (models.create_mixtral_model, models.mixtral_tiny),
}
BUCKET = 32


def _model(family, param_dtype=None):
    create, tiny = {**ADMITTED, **GENERATED}[family]
    return create(tiny(), seq_len=BUCKET, **({"param_dtype": param_dtype} if param_dtype else {}))


@pytest.fixture(scope="module")
def served():
    """One float32 model a family, built once for the file."""
    cache = {}
    return lambda family: cache.setdefault(family, _model(family))


def _padded_block(vocab, real=(13, 21)):
    """Two prompts of a 32-row bucket, zero-padded above their real lengths."""
    rng = np.random.default_rng(41)
    ids = np.zeros((len(real), BUCKET), np.int32)
    for row, n in enumerate(real):
        ids[row, :n] = rng.integers(1, vocab, n)
    return jnp.asarray(ids), jnp.asarray(np.asarray(real, np.int32) - 1)


@pytest.mark.parametrize(
    "family,param_dtype,ulp",
    [(family, None, 2.0 ** -17) for family in (*ADMITTED, *GENERATED)]
    # the served type: the same bfloat16 row through the same matrix, only the product's tiling may differ
    + [(family, "bfloat16", 2.0 ** -8) for family in ("latent_moe_hc", "falcon_h1")],
)
def test_the_indexed_call_returns_that_row_of_the_full_call(family, param_dtype, ulp, served):
    model = served(family) if param_dtype is None else _model(family, param_dtype)
    config = model.module.config
    if family == "falcon_h1":
        assert config.lm_head_multiplier == 0.0078125
    if family == "latent_moe_hc":
        assert config.hc_mult == 4
    ids, at = _padded_block(config.vocab_size)
    full = model.module.apply(model.params, ids)
    rows = model.module.apply(model.params, ids, logits_at=at)
    assert full.shape == (2, BUCKET, config.vocab_size) and rows.shape == (2, 1, config.vocab_size)
    assert rows.dtype == full.dtype
    want = np.stack([np.asarray(full[b, int(at[b])], np.float32) for b in range(2)])[:, None, :]
    assert np.abs(want).max() > 0.02  # falcon's logits are its products / 128
    atol = 4 * ulp * np.abs(want).max()  # four steps of the type at the row's largest logit
    np.testing.assert_allclose(np.asarray(rows, np.float32), want, atol=atol, rtol=0)
    # an index past the block is clamped to its last row, as the engine's `dynamic_slice` was
    past = model.module.apply(model.params, ids, logits_at=jnp.asarray([BUCKET + 5, BUCKET], jnp.int32))
    np.testing.assert_allclose(np.asarray(past[:, 0], np.float32), np.asarray(full[:, -1], np.float32), atol=atol, rtol=0)


def _every_row_then_slice(engine):
    """The engine's cached prefill as it was before PR 41: the module's default
    call — `[1, bucket, V]` logits, the parent's program text — and the sampled
    row cut out of them afterwards."""
    raw = engine._cached_prefill_raw

    def before(params, cache, ids, positions, mask, logits_at):
        logits, cache = raw(params, cache, ids, positions, mask, None)
        return jax.lax.dynamic_slice_in_dim(logits, logits_at[0], 1, axis=1), cache

    engine._cached_prefill_raw = before
    return engine


def _engine(model, **kw):
    return ContinuousBatcher(model, num_slots=2, max_length=64, chunk_size=4, **kw)


def _requests(vocab):
    rng = np.random.default_rng(7)
    lengths = (5, 21, 8, 30)  # buckets 8 and 32, a prompt that fills its bucket among them
    return [Request(i, rng.integers(1, vocab, n).astype(np.int32), max_new_tokens=6) for i, n in enumerate(lengths)]


@pytest.mark.parametrize("family", list(ADMITTED))
def test_an_engine_serves_the_tokens_it_served_with_every_row_computed(family, served):
    model = served(family)
    vocab = model.module.config.vocab_size
    tracer = Tracer(recorder=FlightRecorder())
    engine = _engine(model, tracer=tracer)
    got = engine.run(_requests(vocab))
    want = _every_row_then_slice(_engine(model)).run(_requests(vocab))
    assert sorted(got) == [0, 1, 2, 3]
    for rid in got:  # the first token is the insert's own; the rest ride on the cache it wrote
        assert len(got[rid]) == 6
        np.testing.assert_array_equal(got[rid], want[rid])
    inserts = [r["attrs"] for r in tracer.recorder.records() if r.get("kind") == "span" and r["name"] == "serve.insert"]
    assert sorted(i["bucket"] for i in inserts) == [8, 8, 32, 32]
    assert all(i["head_rows"] == 1 for i in inserts)


def _lowered_insert(engine, bucket):
    args = (
        engine.params, engine._cache, engine._presence, jnp.zeros((1, bucket), jnp.int32),
        _operand(1, np.int32), _operand(0, np.int32), _operand(0, np.int32),
        jnp.asarray(np.zeros((engine.pages_per_slot,), np.int32)),
        _operand(0, np.int32), _operand(1.0, np.float32), _operand(1.0, np.float32),
        engine._rng, engine._new_first_token(),
    )
    return engine._insert_fn(bucket).lower(*args).as_text()


def _full_logits_values(text, bucket, vocab):
    return [shape for shape in (f"tensor<1x{bucket}x{vocab}x", f"tensor<{bucket}x{vocab}x") if shape in text]


@pytest.mark.parametrize("family", list(ADMITTED))
def test_no_lowered_insert_holds_a_bucket_of_logits(family, served):
    model = served(family)
    vocab = model.module.config.vocab_size
    penalty = {"use_repetition_penalty": True, "prefix_cache": False}  # the presence row is seeded beside the sampled row
    for kw in ({}, penalty) if family == "llama" else ({},):
        text = _lowered_insert(_engine(model, **kw), BUCKET)
        assert _full_logits_values(text, BUCKET, vocab) == []
        assert f"tensor<1x1x{vocab}x" in text  # the one row's product
    # the yardstick sees what it is meant to see: the program as it was holds both
    before = _lowered_insert(_every_row_then_slice(_engine(model)), BUCKET)
    assert _full_logits_values(before, BUCKET, vocab)


def test_the_engine_refuses_a_module_that_cannot_take_the_index(served):
    model = served("llama")

    class EveryRow(nn.Module):
        config: models.LlamaConfig

        @nn.compact
        def __call__(self, input_ids, attention_mask=None, positions=None):
            return models.LlamaForCausalLM(self.config, name="inner")(input_ids, attention_mask, positions)

    wrapped = Model.from_flax(EveryRow(model.module.config), {"params": {"inner": model.params["params"]}})
    with pytest.raises(ValueError, match="EveryRow.__call__ takes no `logits_at`"):
        ContinuousBatcher(wrapped, num_slots=2, max_length=64)


@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_generates_prefill_computes_the_last_column_alone(family, served):
    model = served(family)
    vocab = model.module.config.vocab_size
    gen = Generator(model, max_new_tokens=4, max_length=48)
    ids = jnp.asarray(np.random.default_rng(3).integers(1, vocab, (2, 16)).astype(np.int32))
    positions = jnp.broadcast_to(jnp.arange(16)[None, :], (2, 16))
    text = gen._prefill.lower(model.params, ids, positions).as_text()
    assert f"tensor<2x16x{vocab}x" not in text and f"tensor<2x1x{vocab}x" in text
    last, _cache = gen._prefill(model.params, ids, positions)
    full = model.module.apply(model.params, ids)
    np.testing.assert_allclose(np.asarray(last), np.asarray(full[:, -1, :]), atol=2e-5, rtol=0)
