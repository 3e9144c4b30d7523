"""Speculative-decode tests (speculative.py + the Generator/ContinuousBatcher
draft-then-verify paths).

Pins the three load-bearing contracts:
  1. the n-gram drafter only ever proposes verbatim continuations of observed
     context (never out-of-vocab, never past the observed length), and
     degrades to valid_len == 0 — plain decode — on degenerate input;
  2. greedy output is TOKEN-IDENTICAL with speculation on vs off, across
     {llama, gpt_neox} x the page pool's shapes (a page size that does not
     divide the window, a pool too small for the slots, no prefix cache), slot reuse,
     EOS inside a verified block, and the static Generator loop — the
     verification invariant that makes the speedup safe to ship;
  3. the no-recompile discipline survives: one decode executable for the
     engine lifetime with speculation enabled, and the speedup is a measured
     number (accepted_tokens_per_step) wired through the metrics registry.
"""

import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from accelerate_tpu.generation import GenerationConfig, Generator, generate
from accelerate_tpu.models.llama import LlamaConfig, create_llama_model
from accelerate_tpu.serving import ContinuousBatcher, Request
from accelerate_tpu.speculative import greedy_accept_length, propose_ngram_drafts

pytestmark = pytest.mark.speculative


def _model(max_pos=64):
    cfg = LlamaConfig(
        vocab_size=128,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=max_pos,
        rope_theta=10000.0,
    )
    return create_llama_model(cfg, seq_len=32)


def _neox_model(max_pos=64):
    from accelerate_tpu.models.gpt_neox import create_gpt_neox_model, gpt_neox_tiny

    cfg = dataclasses.replace(gpt_neox_tiny(), max_position_embeddings=max_pos)
    return create_gpt_neox_model(cfg, seq_len=32)


def _static_reference(model, prompt, max_new, **kwargs):
    out = np.asarray(generate(model, prompt[None, :], max_new_tokens=max_new, **kwargs))
    return out[0, prompt.size :]


# ------------------------------------------------------------------- drafter
def test_drafter_proposals_are_continuations_of_observed_context():
    """Property sweep: for random histories, every proposal within valid_len
    is the verbatim continuation of the most recent earlier occurrence of the
    trailing n-gram — i.e. drafts[:j] == history[match+m : match+m+j]. In
    particular every proposed token was OBSERVED (in-context, in-vocab)."""
    rng = np.random.default_rng(0)
    for trial in range(50):
        h = int(rng.integers(8, 40))
        hist_len = int(rng.integers(3, h + 1))
        k = int(rng.integers(1, 6))
        m = int(rng.integers(1, 4))
        # small alphabet so n-gram collisions actually happen
        hist = np.zeros((1, h), np.int32)
        hist[0, :hist_len] = rng.integers(1, 6, hist_len)
        drafts, valid = (
            np.asarray(x)
            for x in propose_ngram_drafts(jnp.asarray(hist), jnp.asarray([hist_len], jnp.int32), k, m)
        )
        v = int(valid[0])
        assert 0 <= v <= k
        if v == 0:
            continue
        tail = hist[0, hist_len - m : hist_len]
        # reference: most recent strictly-earlier occurrence of the tail n-gram
        starts = [
            i for i in range(hist_len - m)
            if np.array_equal(hist[0, i : i + m], tail)
        ]
        assert starts, "drafter proposed but no real n-gram match exists"
        j = max(starts)
        expect = hist[0, j + m : min(j + m + k, hist_len)]
        assert v == len(expect[:k]) or v == min(k, hist_len - (j + m))
        np.testing.assert_array_equal(drafts[0, :v], hist[0, j + m : j + m + v])
        assert set(drafts[0, :v]).issubset(set(hist[0, :hist_len].tolist()))


def test_drafter_degenerates_to_no_proposals():
    """No match, context shorter than the n-gram, or a fresh 1-token context
    all yield valid_len == 0 — the verify step then emits exactly one token,
    like plain decode."""
    # all-distinct tokens: the trailing bigram never occurred before
    hist = np.arange(1, 11, dtype=np.int32)[None, :]
    _, valid = propose_ngram_drafts(jnp.asarray(hist), jnp.asarray([10], jnp.int32), 4, 2)
    assert int(np.asarray(valid)[0]) == 0
    # context shorter than the n-gram
    _, valid = propose_ngram_drafts(jnp.asarray(hist), jnp.asarray([1], jnp.int32), 4, 2)
    assert int(np.asarray(valid)[0]) == 0


def test_drafter_respects_observed_length_bound():
    """A match right before the tail has fewer than k observed continuation
    tokens: valid_len must stop at the observed boundary, never proposing the
    unknown future."""
    # history: A B C A B  (tail bigram A B matched at 0, continuation = C only... )
    hist = np.asarray([[7, 8, 9, 7, 8, 0, 0, 0]], np.int32)
    drafts, valid = propose_ngram_drafts(jnp.asarray(hist), jnp.asarray([5], jnp.int32), 4, 2)
    # match at start 0; continuations observed: history[2:5] = [9, 7, 8]
    assert int(np.asarray(valid)[0]) == 3
    np.testing.assert_array_equal(np.asarray(drafts)[0, :3], [9, 7, 8])


def test_drafter_prefers_most_recent_match():
    # bigram (1,2) occurs at 0 (-> 3) and at 4 (-> 5); the tail occurrence at
    # 8 must match position 4's continuation, not position 0's.
    hist = np.asarray([[1, 2, 3, 9, 1, 2, 5, 9, 1, 2]], np.int32)
    drafts, valid = propose_ngram_drafts(jnp.asarray(hist), jnp.asarray([10], jnp.int32), 2, 2)
    assert int(np.asarray(valid)[0]) == 2
    np.testing.assert_array_equal(np.asarray(drafts)[0], [5, 9])


def test_greedy_accept_length_masks_and_prefixes():
    drafts = jnp.asarray([[4, 5, 6], [4, 5, 6], [4, 9, 6], [4, 5, 6]], jnp.int32)
    greedy = jnp.asarray([[4, 5, 6], [4, 5, 9], [4, 5, 6], [4, 5, 6]], jnp.int32)
    valid = jnp.asarray([3, 3, 3, 1], jnp.int32)
    got = np.asarray(greedy_accept_length(drafts, greedy, valid))
    # full match; mismatch at 2; mismatch at 1 (prefix rule, 6==6 at 2 is moot);
    # full match but only 1 valid proposal
    np.testing.assert_array_equal(got, [3, 2, 1, 1])


# ----------------------------------------------------- serving parity sweep
def _count_pool_refusals(engine):
    """How often admission found the pool short (`reserve` -> None) and put a
    request back at the head of the queue: the returned list's one entry."""
    refusals = [0]
    reserve = engine.pool.reserve

    def counting(n):
        pages = reserve(n)
        refusals[0] += pages is None
        return pages

    engine.pool.reserve = counting
    return refusals


@pytest.mark.parametrize("family", ["llama", "gpt_neox"])
@pytest.mark.parametrize(
    "pool",
    [
        pytest.param({}, id="paged"),
        # 32 tokens in pages of 5: the window is padded to 7 pages, 35 tokens
        pytest.param({"page_size": 5}, id="ragged-window"),
        # 8 usable pages of 4 for two slots whose requests take 4 or 5 each
        # (draft window included): admission waits for pages, not for slots
        pytest.param({"page_size": 4, "num_pages": 9}, id="tight-pool"),
        pytest.param({"page_size": 4, "prefix_cache": False}, id="no-prefix-cache"),
    ],
)
def test_serving_greedy_parity_spec_vs_nonspec(family, pool):
    """THE verification invariant: greedy tokens are identical with
    speculation on vs off, per request, across mixed prompt lengths/budgets
    and slot reuse — for both model families and every shape of the pool."""
    model = _model() if family == "llama" else _neox_model()
    vocab = model.module.config.vocab_size
    rng = np.random.default_rng(11)
    lengths = [5, 9, 3, 12, 7]
    budgets = [6, 4, 8, 3, 5]
    prompts = [rng.integers(1, vocab, (n,)).astype(np.int32) for n in lengths]
    requests = lambda: [  # noqa: E731 — rebuilt per engine (ids reused)
        Request(i, p, max_new_tokens=m) for i, (p, m) in enumerate(zip(prompts, budgets))
    ]
    plain = ContinuousBatcher(model, num_slots=2, max_length=32, chunk_size=4, **pool)
    spec = ContinuousBatcher(
        model, num_slots=2, max_length=32, chunk_size=4, speculative=True, draft_tokens=3, **pool
    )
    refusals = _count_pool_refusals(spec)
    ref = plain.run(requests())
    got = spec.run(requests())
    for i in range(len(prompts)):
        np.testing.assert_array_equal(got[i], ref[i])
        assert spec.results[i].finish_reason == plain.results[i].finish_reason
    assert spec._padded_length == (35 if pool.get("page_size") == 5 else 32)
    assert bool(refusals[0]) == ("num_pages" in pool)  # only the tight pool makes a request wait
    assert spec.pool.pages_in_use == 0 and spec.pool.check_consistency() == []


@pytest.mark.parametrize("page_size", [16, 5], ids=["paged", "ragged-window"])
def test_eos_inside_verified_block_matches_one_token_path(page_size):
    """Satellite bugfix pin: an accepted EOS inside a verified block must end
    the request THERE — tail discarded, result ending with the EOS token, the
    same `_trim_at_eos` semantics as the one-token path. draft_tokens=4 with
    chunk_size=3 makes blocks regularly straddle the EOS."""
    model = _model()
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, 128, (6,)).astype(np.int32)
    free_run = _static_reference(model, prompt, 16)
    eos = int(free_run[len(free_run) // 2])
    ref = _static_reference(model, prompt, 16, eos_token_id=eos)
    engine = ContinuousBatcher(
        model, num_slots=2, max_length=32, chunk_size=3, page_size=page_size,
        speculative=True, draft_tokens=4,
    )
    outputs = engine.run([Request(0, prompt, max_new_tokens=16, eos_token_id=eos)])
    np.testing.assert_array_equal(outputs[0], ref)
    assert engine.results[0].finish_reason == "eos"
    assert outputs[0][-1] == eos
    # the discarded tail must not count against anything: a fresh request in
    # the reused slot still matches its own reference
    prompt2 = rng.integers(1, 128, (4,)).astype(np.int32)
    outputs = engine.run([Request(1, prompt2, max_new_tokens=6)])
    np.testing.assert_array_equal(outputs[1], _static_reference(model, prompt2, 6))


def test_decode_compiled_once_with_speculation():
    """The no-recompile discipline survives speculation: one decode executable
    across mixed admissions, insert buckets unchanged, and every accept/reject
    decision a traced op — `trace_counts` is the trace-time witness."""
    model = _model()
    rng = np.random.default_rng(0)
    engine = ContinuousBatcher(
        model, num_slots=2, max_length=64, chunk_size=4, speculative=True, draft_tokens=4
    )
    lengths = [3, 5, 9, 17, 6, 30]
    engine.run(
        [
            Request(i, rng.integers(1, 128, (n,)).astype(np.int32), max_new_tokens=4)
            for i, n in enumerate(lengths)
        ]
    )
    assert engine.trace_counts["decode_chunk"] == 1
    assert engine._chunk_fn._cache_size() == 1
    assert all(r.finished for r in engine.results.values())


def test_accepted_tokens_per_step_is_measured_and_exceeds_one():
    """The speedup is a measured number, not a claim: on a repetitive workload
    (tiny-model greedy decode collapses into loops, prompt-lookup's best case)
    the engine's accepted_tokens_per_step must exceed 1.0, the ledger must
    reconcile (drafted == accepted + rejected), and the histogram must carry
    one observation per verify step."""
    model = _model()
    rng = np.random.default_rng(2)
    engine = ContinuousBatcher(
        model, num_slots=2, max_length=64, chunk_size=4, speculative=True, draft_tokens=4
    )
    engine.run(
        [
            Request(i, rng.integers(1, 128, (6,)).astype(np.int32), max_new_tokens=40)
            for i in range(4)
        ]
    )
    spec = engine.stats["speculative"]
    assert spec["accepted_tokens_per_step"] is not None
    assert spec["accepted_tokens_per_step"] > 1.0, spec
    assert spec["drafted"] == spec["accepted"] + spec["rejected"]
    hist = engine.metrics.get("serving_spec_accepted_tokens")
    assert hist is not None and hist.count == spec["verify_steps"]
    # tokens conservation: every result token came from a verify step (steps +
    # accepted drafts) or was a request's insert-sampled first token
    emitted = sum(len(r.tokens) for r in engine.results.values())
    assert emitted == spec["verify_steps"] + spec["accepted"] + len(engine.results)


def test_speculative_admission_reserves_the_draft_window():
    """Paged admission counts the draft window against the reservation: with
    page_size 4, an (8 prompt + 8 new) request needs 4 pages plain but 5 with
    a 4-token draft window — so a pool of 9 usable pages fits two plain
    requests at once but only one speculative one. Both engines still finish
    everything (reserve-on-admit queues, never deadlocks), token-identically."""
    model = _model()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 128, (8,)).astype(np.int32) for _ in range(2)]
    requests = lambda: [Request(i, p, max_new_tokens=8) for i, p in enumerate(prompts)]  # noqa: E731

    def peak_pages(**kwargs):
        engine = ContinuousBatcher(
            model, num_slots=2, max_length=32, chunk_size=2,
            page_size=4, num_pages=10, prefix_cache=False, **kwargs,
        )
        for r in requests():
            engine.submit(r)
        peak = 0
        while engine.pending:
            engine.step()
            peak = max(peak, engine.pool.pages_in_use)
        outs = {rid: np.asarray(r.tokens, np.int32) for rid, r in engine.results.items()}
        assert engine.pool.pages_in_use == 0
        return peak, outs

    plain_peak, ref = peak_pages()
    spec_peak, got = peak_pages(speculative=True, draft_tokens=4)
    assert plain_peak == 8, plain_peak  # both requests in flight, 4 pages each
    assert spec_peak == 5, spec_peak  # window forces one-at-a-time admission
    for i in range(2):
        np.testing.assert_array_equal(got[i], ref[i])


def test_submit_rejects_when_draft_window_exceeds_pool():
    model = _model()
    engine = ContinuousBatcher(
        model, num_slots=1, max_length=32, chunk_size=2,
        page_size=4, num_pages=5, speculative=True, draft_tokens=4,
    )
    prompt = np.arange(1, 9, dtype=np.int32)
    # 8 prompt + 5 new + 4 window = 17 tokens -> 5 pages > 4 usable
    with pytest.raises(ValueError, match="draft-window"):
        engine.submit(Request(0, prompt, max_new_tokens=5))
    # the same request fits once the window is accounted for
    engine.submit(Request(1, prompt, max_new_tokens=4))
    engine.run()
    assert engine.results[1].finished


def test_speculative_config_validation():
    model = _model()
    with pytest.raises(ValueError, match="greedy-only"):
        ContinuousBatcher(model, num_slots=1, max_length=32, speculative=True, do_sample=True)
    with pytest.raises(ValueError, match="repetition"):
        ContinuousBatcher(
            model, num_slots=1, max_length=32, speculative=True, use_repetition_penalty=True
        )
    with pytest.raises(ValueError, match="draft_tokens"):
        ContinuousBatcher(model, num_slots=1, max_length=32, speculative=True, draft_tokens=0)
    gen = Generator(model, max_new_tokens=8, max_length=32)
    prompt = np.arange(1, 7, dtype=np.int32)[None, :]
    with pytest.raises(ValueError, match="greedy-only"):
        gen(prompt, GenerationConfig(max_new_tokens=4, draft_tokens=2, do_sample=True))
    with pytest.raises(ValueError, match="repetition_penalty"):
        gen(prompt, GenerationConfig(max_new_tokens=4, draft_tokens=2, repetition_penalty=1.5))


# ------------------------------------------------------------ static Generator
def test_generator_speculative_parity_single_and_batch():
    """The fused static loop's draft/verify variant is token-identical to the
    plain loop — batch-1 (full speedup) and batch-3 (lockstep minimum)."""
    model = _model(max_pos=128)
    gen = Generator(model, max_new_tokens=48, max_length=128)
    for seed, (b, n) in enumerate([(1, 48), (3, 24), (1, 7)]):
        p = np.random.default_rng(seed).integers(1, 128, (b, 8)).astype(np.int32)
        ref = np.asarray(gen(p, GenerationConfig(max_new_tokens=n)))
        spec = np.asarray(gen(p, GenerationConfig(max_new_tokens=n, draft_tokens=4)))
        np.testing.assert_array_equal(spec, ref)


def test_generator_speculative_eos_and_trim_parity():
    model = _model(max_pos=128)
    gen = Generator(model, max_new_tokens=48, max_length=128)
    p = np.random.default_rng(0).integers(1, 128, (1, 8)).astype(np.int32)
    free = np.asarray(gen(p, GenerationConfig(max_new_tokens=48)))[0, 8:]
    eos = int(free[len(free) // 2])
    ref = np.asarray(gen(p, GenerationConfig(max_new_tokens=48, eos_token_id=eos)))
    spec = np.asarray(gen(p, GenerationConfig(max_new_tokens=48, eos_token_id=eos, draft_tokens=4)))
    np.testing.assert_array_equal(spec, ref)  # incl. _trim_at_eos truncation


def test_generator_speculative_ragged_left_padded_batch():
    """Left-padded ragged prompts ride the speculative loop too: pads sit in
    the drafter's physical history, but acceptance requires the model's own
    argmax, so parity is unconditional."""
    model = _model(max_pos=128)
    gen = Generator(model, max_new_tokens=16, max_length=128)
    rng = np.random.default_rng(9)
    ids = np.zeros((2, 8), np.int32)
    mask = np.zeros((2, 8), np.int32)
    for row, n in enumerate((5, 8)):
        ids[row, 8 - n :] = rng.integers(1, 128, (n,))
        mask[row, 8 - n :] = 1
    ref = np.asarray(gen(ids, GenerationConfig(max_new_tokens=12), attention_mask=mask))
    spec = np.asarray(
        gen(ids, GenerationConfig(max_new_tokens=12, draft_tokens=3), attention_mask=mask)
    )
    np.testing.assert_array_equal(spec, ref)


def test_generator_one_executable_per_bucket_across_prompt_lengths():
    """Varying prompt lengths must reuse the one compiled speculative loop per
    bucket (the history operand is max_length-sized precisely so prompt width
    never leaks into the decode signature)."""
    model = _model(max_pos=128)
    gen = Generator(model, max_new_tokens=16, max_length=128)
    cfg = GenerationConfig(max_new_tokens=16, draft_tokens=3)
    for n in (4, 6, 11):
        p = np.random.default_rng(n).integers(1, 128, (1, n)).astype(np.int32)
        np.testing.assert_array_equal(
            np.asarray(gen(p, cfg))[0, n:],
            _static_reference(model, p[0], 16),
        )
    assert len([k for k in gen._decode_cache if k[5] == 3]) == 1  # one spec program


def test_seq2seq_rejects_speculation():
    from accelerate_tpu.generation import Seq2SeqGenerator
    from accelerate_tpu.models.t5 import create_t5_model, t5_tiny

    model = create_t5_model(t5_tiny(), seq_len=16)
    gen = Seq2SeqGenerator(model, max_new_tokens=4)
    with pytest.raises(ValueError, match="causal-LM only"):
        gen(np.ones((1, 4), np.int32), GenerationConfig(max_new_tokens=2, draft_tokens=2))
