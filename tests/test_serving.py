"""Continuous-batching serving engine tests (serving.ContinuousBatcher).

Pins the three load-bearing contracts:
  1. ONE decode executable across admissions with varying prompt lengths
     (admission compiles per-bucket inserts, never the chunk program);
  2. in-flight batching: a late-arriving request starts decoding before an
     earlier long request finishes;
  3. greedy outputs are token-identical to the static `Generator` path —
     serving reuses a verified sampler and a verified cache discipline.
"""

import numpy as np
import pytest

import jax

from accelerate_tpu.generation import GenerationConfig, Generator, generate
from accelerate_tpu.models.llama import LlamaConfig, create_llama_model
from accelerate_tpu.serving import ContinuousBatcher, Request


def _model():
    cfg = LlamaConfig(
        vocab_size=128,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=64,
        rope_theta=10000.0,
    )
    return create_llama_model(cfg, seq_len=32)


def _static_reference(model, prompt, max_new, **kwargs):
    """Per-request static path: the generated suffix from the fused Generator."""
    out = np.asarray(generate(model, prompt[None, :], max_new_tokens=max_new, **kwargs))
    return out[0, prompt.size:]


def test_decode_compiled_once_across_mixed_admissions():
    """Varying prompt lengths hit different insert buckets but the decode chunk
    program — the one that runs for the lifetime of the server — never retraces."""
    model = _model()
    rng = np.random.default_rng(0)
    engine = ContinuousBatcher(model, num_slots=2, max_length=64, chunk_size=4)
    lengths = [3, 5, 9, 17, 6, 30]
    requests = [
        Request(i, rng.integers(1, 128, (n,)).astype(np.int32), max_new_tokens=4)
        for i, n in enumerate(lengths)
    ]
    engine.run(requests)
    assert engine.trace_counts["decode_chunk"] == 1
    assert engine._chunk_fn._cache_size() == 1
    # buckets: 3->4, 5->8, 9->16, 17->32, 6->8, 30->32 => {4, 8, 16, 32}
    assert engine.trace_counts["insert"] == 4
    assert set(engine._insert_fns) == {4, 8, 16, 32}
    assert all(r.finished for r in engine.results.values())


def test_late_arrival_starts_before_long_request_finishes():
    model = _model()
    rng = np.random.default_rng(1)
    engine = ContinuousBatcher(model, num_slots=2, max_length=64, chunk_size=4)
    long_prompt = rng.integers(1, 128, (6,)).astype(np.int32)
    engine.submit(Request(0, long_prompt, max_new_tokens=24))
    engine.step()  # request 0 admitted and decoding
    assert not engine.results[0].finished

    # LATE arrival while 0 is mid-flight: it must be admitted into the free slot
    # and stream tokens before 0 completes.
    late_prompt = rng.integers(1, 128, (4,)).astype(np.int32)
    engine.submit(Request(1, late_prompt, max_new_tokens=3))
    events = engine.step()
    assert any(rid == 1 for rid, _ in events), "late request produced no tokens this cycle"
    assert not engine.results[0].finished, "long request should still be in flight"

    outputs = engine.run()  # drain
    assert engine.results[0].finished and engine.results[1].finished
    np.testing.assert_array_equal(outputs[1], _static_reference(model, late_prompt, 3))
    np.testing.assert_array_equal(outputs[0], _static_reference(model, long_prompt, 24))


def test_greedy_parity_with_static_generator_mixed_workload():
    """Every request's greedy tokens are identical to the static Generator path,
    across mixed prompt lengths / budgets and slot reuse."""
    model = _model()
    rng = np.random.default_rng(2)
    lengths = [5, 9, 3, 12, 7]
    budgets = [6, 4, 8, 3, 5]
    prompts = [rng.integers(1, 128, (n,)).astype(np.int32) for n in lengths]
    engine = ContinuousBatcher(model, num_slots=2, max_length=32, chunk_size=4)
    outputs = engine.run(
        [Request(i, p, max_new_tokens=m) for i, (p, m) in enumerate(zip(prompts, budgets))]
    )
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        np.testing.assert_array_equal(outputs[i], _static_reference(model, p, m))


def test_released_slot_sits_at_position_zero_through_reuse():
    """The paged XLA read counts a row's live pages from its position, so a
    slot that `_finish` releases goes back to position 0 (one page: scratch)
    — while greedy decode through slot reuse stays token-identical to the
    static Generator — and the host's count of what each chunk visits rides
    the chunk's span and the `kv_live_page_share` gauge."""
    from accelerate_tpu.telemetry.flight_recorder import FlightRecorder
    from accelerate_tpu.telemetry.tracing import Tracer

    model = _model()
    rng = np.random.default_rng(4)
    lengths = [12, 5, 20, 3, 9]
    budgets = [3, 9, 4, 8, 6]
    prompts = [rng.integers(1, 128, (n,)).astype(np.int32) for n in lengths]
    requests = lambda: [  # noqa: E731 — fresh Request objects per engine
        Request(i, p, max_new_tokens=m) for i, (p, m) in enumerate(zip(prompts, budgets))
    ]
    recorder = FlightRecorder()
    tracer = Tracer(recorder=recorder, category="serve")
    paged = ContinuousBatcher(
        model, num_slots=3, max_length=32, chunk_size=4, page_size=8, tracer=tracer
    )
    for req in requests():
        paged.submit(req)
    shares = []
    while paged.pending:
        paged.step()
        idle = [slot for slot, r in enumerate(paged._slot_request) if r is None]
        assert not paged._slots.pos[idle].any(), (paged._slots.pos, idle)
        assert not paged._slots.page_table[idle].any()
        shares.append(paged.stats["kv_live_page_share"])
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        np.testing.assert_array_equal(
            np.asarray(paged.results[i].tokens), _static_reference(model, p, m)
        )
    assert not paged._slots.pos.any()  # drained: every slot was released to position 0
    chunks = [r for r in recorder.records() if r["name"] == "serve.decode_chunk"]
    assert chunks and all(c["attrs"]["window_pages"] == 3 * 4 for c in chunks)
    # the first chunk: prompts of 12, 5 and 20 tokens, 8 a page -> 2 + 1 + 3 live pages
    assert chunks[0]["attrs"]["live_pages"] == 6 and shares[0] == 0.5
    assert all(0 < c["attrs"]["live_pages"] <= c["attrs"]["window_pages"] for c in chunks)


@pytest.mark.parametrize("run_pages", [1, 2])
def test_chunk_span_read_blocks_is_the_reads_trip_count(monkeypatch, run_pages):
    """`serve.decode_chunk.read_blocks` is the trip count the XLA read's loop
    computes on the device in the chunk's first step — `ceil(n / G)`, `n` the
    live entries of ALL slots (an idle one is one) from the positions the
    chunk is dispatched with — because the engine asks the read's own helper,
    with the operands' numbers the read hands it at trace time. This model's
    two query heads a KV head make an entry a RUN of pages (`read_run_pages`):
    of one page, as multi-head attention lists them, and of two."""
    from accelerate_tpu.ops import attention
    from accelerate_tpu.telemetry.flight_recorder import FlightRecorder
    from accelerate_tpu.telemetry.tracing import Tracer

    model = _model()
    cfg = model.module.config
    kv_heads = getattr(cfg, "num_key_value_heads", cfg.num_attention_heads)
    # blocks of 2 pages of 8 tokens (float32 K): several turns a read
    monkeypatch.setattr(attention, "_READ_BLOCK_BYTES", 2 * 8 * kv_heads * cfg.head_dim * 4)
    monkeypatch.setattr(attention, "_READ_RUN_TOKENS", 8 * run_pages)
    assert attention.read_run_pages(8, cfg.num_attention_heads // kv_heads) == run_pages
    asked = []
    helper = attention.read_block_pages

    def recording(*args):
        asked.append((args, helper(*args)))
        return asked[-1][1]

    monkeypatch.setattr(attention, "read_block_pages", recording)
    recorder = FlightRecorder()
    engine = ContinuousBatcher(
        model, num_slots=4, max_length=32, chunk_size=2, page_size=8,
        tracer=Tracer(recorder=recorder, category="serve"),
    )
    rng = np.random.default_rng(5)
    for i, n in enumerate((20, 7, 15)):  # 3 + 1 + 2 live pages, and one idle slot's one
        engine.submit(Request(i, rng.integers(1, 128, (n,)).astype(np.int32), max_new_tokens=12))
    pushed = []
    push = engine._chunk_operands
    monkeypatch.setattr(
        engine, "_chunk_operands", lambda: (pushed.append(engine._slots.pos.copy()), push())[1]
    )
    engine.step()
    engine.step()
    # the engine's calls (`ops.attention.read_blocks`, a chunk each) and the read's (as the chunk is traced, a layer each)
    assert len(asked) > 1 and len(set(asked)) == 1, asked
    (_, block_pages), = set(asked)
    assert block_pages == 2
    chunks = [r for r in recorder.records() if r["name"] == "serve.decode_chunk"]
    assert len(chunks) == len(pushed) == 2
    for chunk, pos in zip(chunks, pushed):
        n = int((pos // (engine.page_size * run_pages) + 1).sum())  # as the read counts: every slot
        assert chunk["attrs"]["read_blocks"] == -(-n // (block_pages // run_pages))
    # 7 pages; two tokens on, two slots have crossed into a new page: 3 + 2 + 3 + 1 (9 pages, blocks of 2).
    # In runs of two pages 2 + 1 + 1 + 1 entries, then 2 + 1 + 2 + 1, a block each.
    assert [c["attrs"]["read_blocks"] for c in chunks] == {1: [4, 5], 2: [5, 6]}[run_pages]


def test_greedy_parity_gpt_neox_family():
    """The slot-cache decode path is model-layer plumbing (llama AND gpt_neox
    gained the per-row cache write): pin parity on the second family too."""
    import dataclasses

    from accelerate_tpu.models.gpt_neox import create_gpt_neox_model, gpt_neox_tiny

    cfg = dataclasses.replace(gpt_neox_tiny(), max_position_embeddings=64)
    model = create_gpt_neox_model(cfg, seq_len=32)
    rng = np.random.default_rng(8)
    prompts = [rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32) for n in (4, 9)]
    engine = ContinuousBatcher(model, num_slots=2, max_length=32, chunk_size=4)
    outputs = engine.run([Request(i, p, max_new_tokens=5) for i, p in enumerate(prompts)])
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(outputs[i], _static_reference(model, p, 5))


def test_eos_stops_slot_and_matches_static_path():
    model = _model()
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, 128, (6,)).astype(np.int32)
    # pick a token the greedy continuation actually emits so EOS triggers mid-run
    free_run = _static_reference(model, prompt, 8)
    eos = int(free_run[len(free_run) // 2])
    ref = _static_reference(model, prompt, 8, eos_token_id=eos)
    engine = ContinuousBatcher(model, num_slots=2, max_length=32, chunk_size=3)
    outputs = engine.run([Request(0, prompt, max_new_tokens=8, eos_token_id=eos)])
    np.testing.assert_array_equal(outputs[0], ref)
    assert engine.results[0].finish_reason == "eos"
    assert outputs[0][-1] == eos


def test_repetition_penalty_rides_per_slot():
    model = _model()
    rng = np.random.default_rng(4)
    prompt = rng.integers(1, 128, (6,)).astype(np.int32)
    engine = ContinuousBatcher(
        model, num_slots=2, max_length=32, chunk_size=4, use_repetition_penalty=True
    )
    outputs = engine.run(
        [
            Request(0, prompt, max_new_tokens=8, repetition_penalty=1.7),
            Request(1, prompt, max_new_tokens=8, repetition_penalty=1.0),
        ]
    )
    np.testing.assert_array_equal(
        outputs[0], _static_reference(model, prompt, 8, repetition_penalty=1.7)
    )
    np.testing.assert_array_equal(outputs[1], _static_reference(model, prompt, 8))
    # one decode executable even with the presence carry
    assert engine.trace_counts["decode_chunk"] == 1


def test_fewer_decode_iterations_than_static_batching():
    """The headline win: a mixed workload completes in fewer total decode loop
    iterations than static batching. Greedy with no EOS is fully deterministic:
    the static fused loop runs exactly (max_new_of_batch - 1) body iterations per
    batch (the first token comes from prefill), while continuous batching serves
    the short requests inside the long request's shadow."""
    model = _model()
    rng = np.random.default_rng(5)
    budgets = [32, 2, 2, 2, 2, 2, 2, 2]
    prompts = [rng.integers(1, 128, (4,)).astype(np.int32) for _ in budgets]
    num_slots = 2

    # static: batches of `num_slots` in arrival order, each runs to the max budget
    static_iterations = sum(
        max(budgets[i : i + num_slots]) - 1 for i in range(0, len(budgets), num_slots)
    )

    engine = ContinuousBatcher(model, num_slots=num_slots, max_length=64, chunk_size=4)
    outputs = engine.run(
        [Request(i, p, max_new_tokens=m) for i, (p, m) in enumerate(zip(prompts, budgets))]
    )
    assert all(r.finished for r in engine.results.values())
    assert engine.stats["decode_steps"] < static_iterations, (
        engine.stats,
        static_iterations,
    )
    # and the work was not dropped: every request got its full budget
    for i, m in enumerate(budgets):
        assert outputs[i].size == m


def test_streaming_drain_preserves_per_request_order():
    """The packed (slot_id, token) buffer drains time-major: concatenating a
    request's stream events reproduces its final token sequence exactly."""
    model = _model()
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 128, (n,)).astype(np.int32) for n in (5, 8, 3)]
    engine = ContinuousBatcher(model, num_slots=2, max_length=32, chunk_size=3)
    for i, p in enumerate(prompts):
        engine.submit(Request(i, p, max_new_tokens=6))
    streamed = {i: [] for i in range(len(prompts))}
    while engine.pending:
        for rid, toks in engine.step():
            streamed[rid].extend(toks)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(
            np.asarray(streamed[i], np.int32), np.asarray(engine.results[i].tokens, np.int32)
        )
        assert engine.results[i].first_token_time is not None
        assert engine.results[i].finish_time >= engine.results[i].first_token_time


def test_admission_rejects_oversized_and_duplicate_requests():
    model = _model()
    engine = ContinuousBatcher(model, num_slots=2, max_length=16, chunk_size=2)
    prompt = np.arange(1, 13, dtype=np.int32)  # 12 tokens
    with pytest.raises(ValueError, match="slot capacity"):
        engine.submit(Request(0, prompt, max_new_tokens=8))
    engine.submit(Request(1, prompt[:4], max_new_tokens=4))
    with pytest.raises(ValueError, match="duplicate"):
        engine.submit(Request(1, prompt[:4], max_new_tokens=4))
    with pytest.raises(ValueError, match="in flight"):
        engine.release(1)  # not finished yet
    engine.run()
    # release frees host memory AND the id for reuse (long-running servers)
    first = engine.release(1)
    assert first.finished and 1 not in engine.results
    engine.submit(Request(1, prompt[:4], max_new_tokens=4))
    outputs = engine.run()
    np.testing.assert_array_equal(outputs[1], np.asarray(first.tokens, np.int32))


def test_the_contiguous_layout_is_refused_by_the_engine_and_both_clis(capsys):
    """The page pool is the engine's only KV store: `paged=False` (or any
    value but True) raises, `paged=True` is accepted for the benchmark's
    pinned workload files and `engine.paged` reads True, a slot-cache module
    config without a page size cannot be built, and `accelerate-tpu serve` /
    `plan` no longer know `--no-paged` (argparse's own error)."""
    import dataclasses

    from accelerate_tpu.commands.accelerate_cli import get_command_parser

    model = _model()
    for value in (False, None, 0):
        with pytest.raises(ValueError, match="contiguous per-slot KV layout is gone"):
            ContinuousBatcher(model, num_slots=2, max_length=32, paged=value)
    assert ContinuousBatcher(model, num_slots=2, max_length=32, paged=True).paged is True
    with pytest.raises(ValueError, match="decode_page_size"):
        dataclasses.replace(model.module.config, decode_cache_length=32, decode_slot_cache=True)
    parser = get_command_parser()
    for argv in (["serve", "--no-paged"], ["plan", "--no-paged"]):
        with pytest.raises(SystemExit) as exit_info:
            parser.parse_args(argv)
        assert exit_info.value.code == 2
        assert "--no-paged" in capsys.readouterr().err
    assert parser.parse_args(["serve"]).command == "serve"


# ------------------------------------------------- one wait a step (ISSUE 33)
# A step enqueues every insert and the decode chunk before it blocks on
# anything, and blocks once: an admission's first token stays on the device
# until the chunk's own readback.

_ENGINE_KINDS = {
    "plain": {},
    "penalty": {"use_repetition_penalty": True},
    "speculative": {"speculative": True, "draft_tokens": 2},
}


def _count_device_gets(monkeypatch):
    """Every `jax.device_get` from here on appends to the returned list."""
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda tree: (calls.append(tree), real(tree))[1])
    return calls


def _step_attrs(recorder):
    return [r["attrs"] for r in recorder.records() if r["name"] == "serve.step"]


@pytest.mark.parametrize("backlog", [False, True], ids=["no-backlog", "backlog"])
@pytest.mark.parametrize("admissions", [0, 1, 3])
@pytest.mark.parametrize("kind", list(_ENGINE_KINDS))
def test_step_dispatches_everything_and_waits_once(monkeypatch, kind, admissions, backlog):
    """Whatever a step admits, it reads the device back once, after every
    insert and the chunk are enqueued (`waits` / `dispatched_ahead` of
    `serve.step` say the same); a request's first token comes ahead of its
    chunk tokens in the events; nothing is traced twice over the run; and the
    tokens are the static Generator's. With requests left in the queue
    (`backlog`) the step that admits leaves its chunk in flight and reads
    nothing — the first such step — and the NEXT step, which dispatches its
    own chunk behind it, hands these admissions their tokens; a speculative
    engine waits for its own chunk either way."""
    from accelerate_tpu.telemetry.flight_recorder import FlightRecorder
    from accelerate_tpu.telemetry.tracing import Tracer

    model = _model()
    rng = np.random.default_rng(33)
    recorder = FlightRecorder()
    engine = ContinuousBatcher(
        model, num_slots=4, max_length=64, chunk_size=3,
        tracer=Tracer(recorder=recorder, category="serve"), **_ENGINE_KINDS[kind],
    )
    ahead = backlog and kind != "speculative"
    assert engine.stats["run_ahead"]["enabled"] is (kind != "speculative")
    assert ("speculative" in (engine.stats["run_ahead"]["disabled_reason"] or "")) is (kind == "speculative")
    penalty = {"repetition_penalty": 1.5} if kind == "penalty" else {}
    resident = rng.integers(1, 128, (5,)).astype(np.int32)
    engine.submit(Request(100, resident, max_new_tokens=30, **penalty))
    engine.step()  # the resident request decodes through the step under test
    prompts = [rng.integers(1, 128, (n,)).astype(np.int32) for n in (6, 3, 11)[:admissions]]
    for i, p in enumerate(prompts):
        engine.submit(Request(i, p, max_new_tokens=6, **penalty))
    queued = {}
    if backlog:  # fill the other slots and leave two requests waiting
        for i in range(200, 200 + (3 - admissions) + 2):
            queued[i] = rng.integers(1, 128, (4,)).astype(np.int32)
            engine.submit(Request(i, queued[i], max_new_tokens=7, **penalty))
    fillers = 3 - admissions if backlog else 0

    calls = _count_device_gets(monkeypatch)
    steps_before = len(_step_attrs(recorder))
    events = engine.step()
    step = _step_attrs(recorder)[steps_before]
    assert (step["inserts"], step["dispatched_ahead"]) == (admissions + fillers, admissions + fillers + 1)
    if ahead:
        # the step that starts running ahead: everything enqueued, nothing read
        assert len(calls) == 0 and events == []
        assert (step["waits"], step["in_flight_at_return"]) == (0, 1) and len(engine._flights) == 1
        events = engine.step()  # dispatches its chunk behind that one, then reads that one
        step = _step_attrs(recorder)[steps_before + 1]
        assert (step["inserts"], step["dispatched_ahead"], step["in_flight_at_return"]) == (0, 1, 1)
    assert len(calls) == 1 and step["waits"] == 1
    monkeypatch.undo()
    if not ahead:
        assert step["in_flight_at_return"] == 0 and not engine._flights
    assert not engine._fresh  # every admission's first token has been handed out

    by_request = {}
    for rid, toks in events:
        by_request.setdefault(rid, []).append(toks)
    assert set(by_request) == {100, *range(admissions), *list(queued)[:fillers]}
    for i in range(admissions):
        first, *rest = by_request[i]
        assert len(first) == 1 and rest, by_request[i]  # the first token, then the chunk's
        assert first + [t for toks in rest for t in toks] == engine.results[i].tokens
        if kind == "speculative":  # the drafter's context, first token included
            slot, n = engine._slot_of(i), len(engine.results[i].tokens)
            np.testing.assert_array_equal(
                engine._slots.history[slot, : prompts[i].size + n],
                np.concatenate([prompts[i], engine.results[i].tokens]),
            )
    # stream order: the admissions' first tokens, in admission order, lead the step's events
    assert [rid for rid, _ in events[:admissions]] == list(range(admissions))

    outputs = engine.run()
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(outputs[i], _static_reference(model, p, 6, **penalty))
    for i, p in queued.items():
        np.testing.assert_array_equal(outputs[i], _static_reference(model, p, 7, **penalty))
    np.testing.assert_array_equal(outputs[100], _static_reference(model, resident, 30, **penalty))
    assert engine.trace_counts["decode_chunk"] == 1  # one chunk program, running ahead or not
    assert engine.trace_counts["insert"] == len(engine._insert_fns) == len(
        {8, *(8, 4, 16)[:admissions], *([4] if backlog else [])})
    assert engine.stats["waits_per_step"] == 1.0 and not engine.pending
    assert (engine.stats["chunks_ahead_share"] > 0) is ahead
    assert all(s["waits"] <= 1 for s in _step_attrs(recorder))
    idle = engine.step()  # nothing queued, nothing active: no dispatch, no wait
    assert idle == [] and engine.stats["waits_per_step"] == 1.0
    assert _step_attrs(recorder)[-1]["waits"] == 0


def _first_greedy_token(model, prompt):
    return int(_static_reference(model, prompt, 1)[0])


@pytest.mark.parametrize("backlog", [False, True], ids=["no-backlog", "backlog"])
@pytest.mark.parametrize("kind", list(_ENGINE_KINDS))
def test_first_token_eos_ends_the_request_on_the_device(kind, backlog):
    """A first token that is the request's EOS: the host has not seen it when
    it pushes the chunk's operands, so the chunk clears the slot itself — the
    request ends with that one token, as "eos", beside a neighbour that
    decodes on undisturbed. With a request waiting behind them the chunk is
    read a step later, and the slot the host had predicted busy has by then
    sat out the chunk dispatched meanwhile: counted."""
    model = _model()
    rng = np.random.default_rng(34)
    prompt, other, waiting = (rng.integers(1, 128, (n,)).astype(np.int32) for n in (6, 9, 5))
    eos = _first_greedy_token(model, prompt)
    engine = ContinuousBatcher(model, num_slots=2, max_length=32, chunk_size=3, **_ENGINE_KINDS[kind])
    engine.submit(Request(0, prompt, max_new_tokens=8, eos_token_id=eos))
    engine.submit(Request(1, other, max_new_tokens=7))
    if backlog:
        engine.submit(Request(2, waiting, max_new_tokens=4))
    events = engine.step()
    ahead = backlog and kind != "speculative"
    if ahead:
        assert events == [] and not engine.results[0].finished  # still on the device
        events = engine.step()
    assert events[0] == (0, [eos]) and [rid for rid, _ in events].count(0) == 1
    assert engine.results[0].finished and engine.results[0].finish_reason == "eos"
    assert engine.results[0].tokens == [eos]
    assert engine.stats["slot_chunks_lost_to_eos"] == (1 if ahead else 0)
    if not backlog:
        assert engine.free_slots == 1 and engine.pool.pages_in_use == len(engine._slot_pages[1])
    outputs = engine.run()
    np.testing.assert_array_equal(outputs[1], _static_reference(model, other, 7))
    if backlog:
        np.testing.assert_array_equal(outputs[2], _static_reference(model, waiting, 4))
    assert engine.pool.pages_in_use == 0 and engine.stats["slot_chunks_lost_to_eos"] == (1 if ahead else 0)


@pytest.mark.parametrize("slots", [1, 2, 3])
def test_one_token_requests_in_one_step_each_get_their_own_token(monkeypatch, slots):
    """A `max_new_tokens == 1` admission holds its slot until the step's drain,
    so that two of them admitted together never share an entry of the
    first-token buffer: with one slot they take a step each, with more they
    share a step — whose only work is their inserts: no chunk, and the one
    wait is the read of the buffer alone. Pages all come back. With fewer
    slots than requests a queue waits behind them — and with nothing to
    decode there is no chunk to leave in flight: every step reads its own."""
    model = _model()
    rng = np.random.default_rng(35)
    prompts = [rng.integers(1, 128, (n,)).astype(np.int32) for n in (5, 12, 7)]
    expected = [_first_greedy_token(model, p) for p in prompts]
    assert len(set(expected)) > 1, "the prompts must tell the slots apart"
    engine = ContinuousBatcher(model, num_slots=slots, max_length=32, chunk_size=3)
    for i, p in enumerate(prompts):
        engine.submit(Request(i, p, max_new_tokens=1))
    calls = _count_device_gets(monkeypatch)
    steps = []
    while engine.pending:
        steps.append(engine.step())
        assert not engine._flights
    assert [len(events) for events in steps] == {1: [1, 1, 1], 2: [2, 1], 3: [3]}[slots]
    assert len(calls) == len(steps)  # one wait a step
    assert [e for events in steps for e in events] == [(i, [t]) for i, t in enumerate(expected)]
    assert all(r.finish_reason == "length" and r.first_token_time is not None
               for r in engine.results.values())
    assert engine.stats["chunks"] == 0 and engine.stats["waits_per_step"] == 1.0
    assert engine.free_slots == slots and engine.pool.pages_in_use == 0


def test_one_token_request_beside_a_decoding_slot_and_a_prefix_hit():
    """One step admits a one-token request (which the chunk must see as an
    idle slot) and a longer one; a later request shares the longer one's
    prompt prefix from the prefix cache, whose registration needed no token.
    All match the static path."""
    model = _model()
    rng = np.random.default_rng(36)
    shared = rng.integers(1, 128, (16,)).astype(np.int32)  # two full pages of 8
    long_a = np.concatenate([shared, rng.integers(1, 128, (3,)).astype(np.int32)])
    long_b = np.concatenate([shared, rng.integers(1, 128, (5,)).astype(np.int32)])
    single = rng.integers(1, 128, (9,)).astype(np.int32)
    engine = ContinuousBatcher(model, num_slots=3, max_length=64, chunk_size=3, page_size=8)
    engine.submit(Request(0, single, max_new_tokens=1))
    engine.submit(Request(1, long_a, max_new_tokens=6))
    events = engine.step()
    assert [rid for rid, _ in events[:2]] == [0, 1] and engine.results[0].finished
    assert engine.free_slots == 2
    engine.submit(Request(2, long_b, max_new_tokens=5))
    engine.submit(Request(3, long_b, max_new_tokens=1))  # a one-token request off the cache
    outputs = engine.run()
    assert engine.stats["prefix_cache"]["hits"] >= 4  # two pages, twice
    for rid, (p, m) in enumerate([(single, 1), (long_a, 6), (long_b, 5), (long_b, 1)]):
        np.testing.assert_array_equal(outputs[rid], _static_reference(model, p, m))
    assert engine.pool.pages_in_use == 0


# ------------------------------------------- one chunk ahead under a backlog (ISSUE 35)
# While requests wait in the queue a step leaves its decode chunk in flight and
# the next step enqueues its inserts and its own chunk BEHIND it before it
# reads it back; slot state is carried on the device, the host works from a
# predicted mirror. With an empty queue a step is what it was.


def _recorded_engine(model, **kwargs):
    from accelerate_tpu.telemetry.flight_recorder import FlightRecorder
    from accelerate_tpu.telemetry.tracing import Tracer

    recorder = FlightRecorder()
    return ContinuousBatcher(model, tracer=Tracer(recorder=recorder, category="serve"), **kwargs), recorder


def _serve(engine, requests, backlog):
    """Step `requests` through `engine` to the end: all submitted at once (more
    than the slots take: a backlog), or each only when a slot is free for it,
    so that no step ever finds a request left in its queue. Returns the events
    of every step."""
    waiting, steps = list(requests), []
    while waiting or engine.pending:
        while waiting and (backlog or engine.free_slots > engine.queue_depth):
            engine.submit(waiting.pop(0))
        steps.append(engine.step())
    return steps


_FAMILIES = {
    "pythia-tiny": ("gpt-neox-tiny", {}),
    "latent-tiny": ("latent-moe-tiny", {}),
    "olmo-hybrid-tiny": ("olmo-hybrid-tiny", {}),
    "falcon-h1-tiny": ("falcon-h1-tiny", {}),
}


@pytest.mark.parametrize("sampling", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("family", list(_FAMILIES))
def test_a_backlog_is_served_the_synchronous_paths_tokens(family, sampling):
    """Ten requests through two slots, submitted at once (the engine runs a
    chunk ahead on nearly every step) and as slots free up (it never does):
    the same programs run on the same operands in the same order, so every
    request's tokens are equal — greedy, and sampled from one seed at
    per-request temperatures — for a page-only family, the latent family and
    the family with by-slot state. One decode program either way."""
    from accelerate_tpu.models import create_named_model

    name, kwargs = _FAMILIES[family]
    model = create_named_model(name, **kwargs)
    vocab = model.module.config.vocab_size
    rng = np.random.default_rng(351)
    lengths, budgets = (5, 17, 9, 3, 12, 7, 20, 4, 11, 6), (9, 4, 13, 6, 5, 11, 3, 8, 1, 7)

    def requests():
        return [Request(i, rng_i, max_new_tokens=m, temperature=0.7 + 0.1 * i if sampling else 1.0)
                for i, (rng_i, m) in enumerate(zip(prompts, budgets))]

    prompts = [rng.integers(1, vocab, (n,)).astype(np.int32) for n in lengths]
    sampler = {"do_sample": True, "top_k": 8, "rng": jax.random.key(35)} if sampling else {}
    served = {}
    for backlog in (True, False):
        engine, recorder = _recorded_engine(model, num_slots=2, max_length=48, chunk_size=4, page_size=8, **sampler)
        _serve(engine, requests(), backlog)
        served[backlog] = {i: list(r.tokens) for i, r in engine.results.items()}
        assert all(r.finish_reason == "length" for r in engine.results.values())
        assert engine.trace_counts["decode_chunk"] == 1 and engine.stats["waits_per_step"] == 1.0
        share = engine.stats["chunks_ahead_share"]
        assert share >= 0.5 if backlog else share == 0.0
        in_flight = [a["in_flight_at_return"] for a in _step_attrs(recorder)]
        assert max(in_flight) == (1 if backlog else 0) and in_flight[-1] == 0
        assert engine.pool.pages_in_use == 0 and engine.stats["slot_chunks_lost_to_eos"] == 0
    assert served[True] == served[False]
    assert [len(served[True][i]) for i in range(len(budgets))] == list(budgets)


def test_a_slot_predicted_free_is_given_away_before_its_last_tokens_are_drained(monkeypatch):
    """A request that ends by length is known a chunk early: its slot, its
    pages and its table row are vacated when its last chunk is DISPATCHED, the
    next queued request's insert is enqueued behind that chunk, and only the
    drain that hands out the last tokens finishes the result — until then it
    reads unfinished and `release()` refuses it."""
    model = _model()
    rng = np.random.default_rng(352)
    prompts = [rng.integers(1, 128, (n,)).astype(np.int32) for n in (5, 9, 6)]
    engine = ContinuousBatcher(model, num_slots=1, max_length=32, chunk_size=4, page_size=8)
    for i, (p, m) in enumerate(zip(prompts, (6, 9, 3))):
        engine.submit(Request(i, p, max_new_tokens=m))
    assert engine.step() == [] and engine._slot_of(0) == 0  # chunk 1 of request 0 in flight
    events = engine.step()  # chunk 2 — its last token — dispatched behind it; chunk 1 read
    assert [toks for rid, toks in events if rid == 0] == [engine.results[0].tokens[:1], engine.results[0].tokens[1:5]]
    assert engine.free_slots == 1 and engine.pool.pages_in_use == 0  # vacated on the prediction
    assert not engine._slots.pos.any() and not engine._slots.page_table.any()
    assert not engine.results[0].finished and len(engine.results[0].tokens) == 5
    with pytest.raises(ValueError, match="in flight"):
        engine.release(0)
    tenants_at_drain = []
    drain = engine._drain
    monkeypatch.setattr(engine, "_drain", lambda *a: (
        tenants_at_drain.append((engine._slot_request[0].request_id, engine.results[0].finished)), drain(*a))[1])
    events = engine.step()
    monkeypatch.undo()
    assert tenants_at_drain == [(1, False)]  # request 1 holds the slot before request 0's last token is drained
    assert (0, [engine.results[0].tokens[-1]]) in events
    result = engine.release(0)
    assert result.finished and result.finish_reason == "length"
    np.testing.assert_array_equal(result.tokens, _static_reference(model, prompts[0], 6))
    outputs = engine.run()
    for i, m in ((1, 9), (2, 3)):
        np.testing.assert_array_equal(outputs[i], _static_reference(model, prompts[i], m))
    assert engine.stats["finish_reasons"]["length"] == 3 and engine.pool.pages_in_use == 0


def test_an_eos_stop_costs_one_chunk_of_one_slot_under_a_backlog():
    """The host cannot predict an EOS: under a backlog it learns of it one
    chunk late, and the chunk already in flight carries the slot inactive —
    one chunk of one slot, counted by `slot_chunks_lost_to_eos`, never paid
    with an empty queue — while every request's tokens are what the
    synchronous path serves."""
    model = _model()
    rng = np.random.default_rng(353)
    prompts = [rng.integers(1, 128, (n,)).astype(np.int32) for n in (6, 9, 4, 7, 5)]
    free_run = _static_reference(model, prompts[0], 12)
    eos = int(free_run[4])  # lands inside its second chunk of 3, six tokens short of its budget
    assert eos not in free_run[:4]

    def requests():
        return [Request(0, prompts[0], max_new_tokens=12, eos_token_id=eos)] + [
            Request(i, p, max_new_tokens=7) for i, p in enumerate(prompts) if i]

    stats, served = {}, {}
    for backlog in (True, False):
        engine = ContinuousBatcher(model, num_slots=2, max_length=32, chunk_size=3)
        _serve(engine, requests(), backlog)
        stats[backlog], served[backlog] = engine.stats, {i: list(r.tokens) for i, r in engine.results.items()}
        assert engine.results[0].finish_reason == "eos" and engine.pool.pages_in_use == 0
    np.testing.assert_array_equal(served[True][0], _static_reference(model, prompts[0], 12, eos_token_id=eos))
    assert served[True] == served[False]
    assert stats[True]["slot_chunks_lost_to_eos"] == 1 and stats[False]["slot_chunks_lost_to_eos"] == 0
    assert stats[True]["chunks"] <= stats[False]["chunks"] + 1


@pytest.mark.faults
@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_cancel_and_deadline_with_a_chunk_in_flight(how):
    """A request that is cancelled, or times out, while its chunk is in flight:
    no token of it is handed out by any later step (the chunk streams on; the
    drain drops them), the device is told with the next dispatch, and its
    pages — free for the next admission at once — are written by their next
    tenant's insert only after the chunk in flight, so every other request is
    served the static path's tokens."""
    model = _model()
    rng = np.random.default_rng(354)
    prompts = [rng.integers(1, 128, (n,)).astype(np.int32) for n in (5, 7, 6, 4, 9)]
    engine = ContinuousBatcher(model, num_slots=2, max_length=32, chunk_size=2, num_pages=5)
    for i, p in enumerate(prompts):
        engine.submit(Request(i, p, max_new_tokens=10, deadline_s=1000.0 if i == 0 else None))
    engine.step(), engine.step(), engine.step()
    assert engine._flights and engine._flights[0].tenants[engine._slot_of(0)] is engine.results[0]
    kept, pages = list(engine.results[0].tokens), list(engine._slot_pages[engine._slot_of(0)])
    assert kept and pages
    if how == "cancel":
        assert engine.cancel(0) is True
        assert engine.results[0].finished and engine.free_slots == 1
    else:
        engine._deadlines[0] = 0.0  # the next step's sweep finds it expired
    events = engine.step()  # reads the chunk that still held request 0; admits request 2 into its slot
    assert all(rid != 0 for rid, _ in events)
    assert engine.results[0].finish_reason == ("cancelled" if how == "cancel" else "timeout")
    assert engine._slot_of(2) is not None and set(engine._slot_pages[engine._slot_of(2)]) & set(pages)
    later = [rid for events in _serve(engine, [], backlog=True) for rid, _ in events]
    assert 0 not in later and engine.results[0].tokens == kept
    for i in range(1, 5):
        np.testing.assert_array_equal(engine.results[i].tokens, _static_reference(model, prompts[i], 10))
    assert engine.pool.pages_in_use == 0 and not engine.pending


@pytest.mark.parametrize("how", ["drain", "close", "run"])
def test_lifecycle_calls_with_a_chunk_in_flight(how):
    """A chunk a step left running is pending work: `run()` and `drain()` step
    until it is read back and everything finishes; `close()` reads it back —
    its tokens reach their results — before it cancels what is left."""
    model = _model()
    rng = np.random.default_rng(355)
    prompts = [rng.integers(1, 128, (n,)).astype(np.int32) for n in (5, 8, 4)]
    engine = ContinuousBatcher(model, num_slots=1, max_length=32, chunk_size=3)
    for i, p in enumerate(prompts):
        engine.submit(Request(i, p, max_new_tokens=6))
    assert engine.step() == [] and engine._flights and engine.pending
    if how == "close":
        results = engine.close()
        np.testing.assert_array_equal(results[0].tokens, _static_reference(model, prompts[0], 6)[:4])
        assert [results[i].finish_reason for i in range(3)] == ["cancelled"] * 3
        assert results[0].first_token_time is not None and not results[1].tokens
        assert engine.closed and not engine.pending and not engine._flights
        assert engine.pool.pages_in_use == 0 and engine.stats["waits_per_step"] == 1.0
        return
    results = engine.drain() if how == "drain" else (engine.run(), engine.results)[1]
    assert not engine.pending and not engine._flights
    for i, p in enumerate(prompts):
        assert results[i].finish_reason == "length"
        np.testing.assert_array_equal(results[i].tokens, _static_reference(model, p, 6))
    engine.submit(Request(3, prompts[0], max_new_tokens=2))  # reopened, and synchronous again
    assert [rid for rid, _ in engine.step()] == [3, 3] and not engine._flights


@pytest.mark.faults
def test_a_failing_chunk_condemns_the_successor_dispatched_behind_it(monkeypatch):
    """A failure that surfaces at one chunk's readback while its successor —
    and the inserts enqueued between them — is already in flight: they
    consumed the donated cache, so both steps' requests error (a request
    vacated on a prediction, whose last tokens that readback held, too); the
    engine keeps serving what was queued."""
    model = _model()
    rng = np.random.default_rng(356)
    prompts = [rng.integers(1, 128, (n,)).astype(np.int32) for n in (5, 7, 6, 4)]
    engine = ContinuousBatcher(model, num_slots=2, max_length=32, chunk_size=3)
    for i, (p, m) in enumerate(zip(prompts, (3, 9, 5, 4))):
        engine.submit(Request(i, p, max_new_tokens=m))
    assert engine.step() == []  # requests 0 and 1 in the chunk in flight; 0 ends in it: vacated
    assert engine.free_slots == 1 and not engine.results[0].finished

    def dying_read(tree):
        raise RuntimeError("device halted in a chunk")

    monkeypatch.setattr(jax, "device_get", dying_read)
    assert engine.step() == []  # admits 2, dispatches the successor, then reads the first chunk: dies
    monkeypatch.undo()
    for rid in (0, 1, 2):
        assert engine.results[rid].finish_reason == "error", rid
        assert "device halted" in engine.results[rid].error and engine.results[rid].tokens == []
    assert not engine.results[3].finished and not engine._flights and not engine._fresh
    assert engine.free_slots == 2 and engine.pool.pages_in_use == 0 and not engine._slots.active.any()
    engine.submit(Request(4, prompts[1], max_new_tokens=6))
    outputs = engine.run()
    np.testing.assert_array_equal(outputs[3], _static_reference(model, prompts[3], 4))
    np.testing.assert_array_equal(outputs[4], _static_reference(model, prompts[1], 6))
    assert engine.stats["finish_reasons"]["error"] == 3 and engine.stats["waits_per_step"] == 1.0


# ------------------------------------------------------------- fault isolation
# The serving-hardening contract: the engine degrades PER-REQUEST (deadline,
# cancel, backpressure, admission/step errors), never per-process.


@pytest.mark.faults
def test_queued_deadline_expires_without_occupying_a_slot():
    model = _model()
    rng = np.random.default_rng(10)
    engine = ContinuousBatcher(model, num_slots=1, max_length=32, chunk_size=2)
    prompt = rng.integers(1, 128, (4,)).astype(np.int32)
    engine.submit(Request(0, prompt, max_new_tokens=4, deadline_s=0.0))  # already expired
    engine.submit(Request(1, prompt, max_new_tokens=4))
    outputs = engine.run()
    assert engine.results[0].finish_reason == "timeout"
    assert engine.results[0].tokens == []  # never admitted
    assert engine.results[1].finish_reason == "length"
    np.testing.assert_array_equal(outputs[1], _static_reference(model, prompt, 4))


@pytest.mark.faults
def test_inflight_deadline_keeps_partial_tokens_and_frees_slot():
    model = _model()
    rng = np.random.default_rng(11)
    engine = ContinuousBatcher(model, num_slots=1, max_length=64, chunk_size=2)
    prompt = rng.integers(1, 128, (4,)).astype(np.int32)
    engine.submit(Request(0, prompt, max_new_tokens=24, deadline_s=1000.0))
    engine.step()  # admitted + some decode progress
    partial = len(engine.results[0].tokens)
    assert partial >= 1 and not engine.results[0].finished
    engine._deadlines[0] = 0.0  # force the wall clock past the deadline
    engine.step()
    result = engine.results[0]
    assert result.finish_reason == "timeout"
    assert len(result.tokens) >= partial  # partial output kept, never discarded
    assert engine.free_slots == 1  # the slot is serviceable again
    # and the freed slot serves the next request with exact greedy parity
    engine.submit(Request(1, prompt, max_new_tokens=4))
    outputs = engine.run()
    np.testing.assert_array_equal(outputs[1], _static_reference(model, prompt, 4))


@pytest.mark.faults
@pytest.mark.parametrize("backlog", [False, True], ids=["no-backlog", "backlog"])
def test_cancel_queued_and_inflight_requests(backlog):
    """Cancel while queued: no tokens at all. Cancel mid-flight: the tokens
    handed out so far are kept — with a request still queued behind it the
    engine has a chunk in flight, whose tokens for the cancelled request no
    later step hands out."""
    model = _model()
    rng = np.random.default_rng(12)
    engine = ContinuousBatcher(model, num_slots=1, max_length=64, chunk_size=2)
    prompt = rng.integers(1, 128, (4,)).astype(np.int32)
    engine.submit(Request(0, prompt, max_new_tokens=24))
    if backlog:
        engine.submit(Request(1, prompt, max_new_tokens=4))
    engine.step()  # 0 in flight, 1 queued
    engine.step()
    if not backlog:
        engine.submit(Request(1, prompt, max_new_tokens=4))  # queued, and no step has seen it
    assert engine.cancel(1) is True  # cancel while queued: no tokens at all
    assert engine.results[1].finish_reason == "cancelled"
    assert engine.results[1].tokens == []
    assert bool(engine._flights) is backlog
    kept = list(engine.results[0].tokens)
    assert engine.cancel(0) is True  # cancel mid-flight: partial tokens kept
    assert engine.results[0].finish_reason == "cancelled"
    assert kept and engine.free_slots == 1
    assert engine.cancel(0) is False  # already finished
    with pytest.raises(KeyError):
        engine.cancel(99)
    engine.submit(Request(2, prompt, max_new_tokens=4))
    streamed = [rid for _ in range(32) if engine.pending for rid, _ in engine.step()]
    assert 0 not in streamed and engine.results[0].tokens == kept  # the chunk in flight streamed on: dropped
    np.testing.assert_array_equal(engine.results[2].tokens, _static_reference(model, prompt, 4))
    assert not engine.pending and engine.pool.pages_in_use == 0


@pytest.mark.faults
def test_bounded_queue_raises_queue_full():
    from accelerate_tpu.serving import QueueFull

    model = _model()
    rng = np.random.default_rng(13)
    engine = ContinuousBatcher(model, num_slots=1, max_length=32, chunk_size=2, max_queue=2)
    prompt = rng.integers(1, 128, (4,)).astype(np.int32)
    engine.submit(Request(0, prompt, max_new_tokens=4))
    engine.submit(Request(1, prompt, max_new_tokens=4))
    with pytest.raises(QueueFull):
        engine.submit(Request(2, prompt, max_new_tokens=4))
    assert 2 not in engine.results, "rejected request must leave no result entry"
    engine.step()  # admission drains the queue; capacity opens up
    engine.submit(Request(2, prompt, max_new_tokens=4))
    engine.run()
    assert engine.stats["queue_peak"] == 2
    assert all(engine.results[i].finish_reason == "length" for i in range(3))


@pytest.mark.faults
def test_insert_error_isolated_to_one_request():
    """A device error while admitting ONE request (here: its bucket's insert
    executable dies) errors only that request; every other request still
    matches the static path token-for-token."""
    model = _model()
    rng = np.random.default_rng(14)
    engine = ContinuousBatcher(model, num_slots=2, max_length=64, chunk_size=2)
    good_a = rng.integers(1, 128, (4,)).astype(np.int32)   # bucket 4
    poison = rng.integers(1, 128, (7,)).astype(np.int32)   # bucket 8
    good_b = rng.integers(1, 128, (3,)).astype(np.int32)   # bucket 4

    real_insert_fn = engine._insert_fn

    def poisoned_insert_fn(bucket):
        if bucket == 8:
            raise RuntimeError("injected transient device error")
        return real_insert_fn(bucket)

    engine._insert_fn = poisoned_insert_fn
    outputs = engine.run(
        [
            Request(0, good_a, max_new_tokens=4),
            Request(1, poison, max_new_tokens=4),
            Request(2, good_b, max_new_tokens=4),
        ]
    )
    assert engine.results[1].finish_reason == "error"
    assert "injected transient device error" in engine.results[1].error
    assert engine.results[1].tokens == []
    np.testing.assert_array_equal(outputs[0], _static_reference(model, good_a, 4))
    np.testing.assert_array_equal(outputs[2], _static_reference(model, good_b, 4))
    assert engine.stats["finish_reasons"]["error"] == 1
    assert engine.stats["finish_reasons"]["length"] == 2


@pytest.mark.faults
def test_chunk_dispatch_failure_errors_inflight_but_engine_survives():
    """The blast-radius exception: the ONE shared decode executable dying takes
    every in-flight request with it — but the engine stays up and the next
    admission serves correctly from freshly-rebuilt cache rows."""
    model = _model()
    rng = np.random.default_rng(15)
    engine = ContinuousBatcher(model, num_slots=2, max_length=64, chunk_size=2)
    prompts = [rng.integers(1, 128, (4,)).astype(np.int32) for _ in range(2)]
    for i, p in enumerate(prompts):
        engine.submit(Request(i, p, max_new_tokens=8))
    engine.step()  # both admitted and decoding

    real_chunk_fn = engine._chunk_fn
    engine._chunk_fn = lambda *a, **k: (_ for _ in ()).throw(RuntimeError("XLA dispatch died"))
    engine.step()
    engine._chunk_fn = real_chunk_fn

    for i in range(2):
        assert engine.results[i].finish_reason == "error"
        assert "XLA dispatch died" in engine.results[i].error
        assert engine.results[i].tokens, "partial tokens must be kept"
    assert engine.free_slots == 2 and not engine.pending

    engine.submit(Request(2, prompts[0], max_new_tokens=4))
    outputs = engine.run()
    np.testing.assert_array_equal(outputs[2], _static_reference(model, prompts[0], 4))


@pytest.mark.faults
@pytest.mark.parametrize("decoding", [True, False])
def test_device_failure_at_the_steps_wait_errors_admissions_and_inflight(monkeypatch, decoding):
    """An insert's device-side failure surfaces at the step's one wait, where
    it cannot be told from the chunk's: the requests admitted in that step
    error with no tokens, the in-flight ones keep their partial tokens, and
    the engine serves the next request correctly — whether the wait was the
    chunk's readback or (a step of one-token admissions) the buffer's alone."""
    model = _model()
    rng = np.random.default_rng(19)
    engine = ContinuousBatcher(model, num_slots=3, max_length=64, chunk_size=2)
    prompts = [rng.integers(1, 128, (4,)).astype(np.int32) for _ in range(3)]
    if decoding:
        engine.submit(Request(0, prompts[0], max_new_tokens=8))
        engine.step()
    engine.submit(Request(1, prompts[1], max_new_tokens=6 if decoding else 1))
    engine.submit(Request(2, prompts[2], max_new_tokens=1))

    def dying_read(tree):
        raise RuntimeError("device halted in an insert")

    monkeypatch.setattr(jax, "device_get", dying_read)
    assert engine.step() == []
    monkeypatch.undo()

    for rid in (1, 2):
        assert engine.results[rid].finish_reason == "error"
        assert "device halted" in engine.results[rid].error and engine.results[rid].tokens == []
    if decoding:
        assert engine.results[0].finish_reason == "error" and engine.results[0].tokens
    assert engine.free_slots == 3 and not engine.pending and not engine._fresh
    assert engine.pool.pages_in_use == 0

    engine.submit(Request(3, prompts[0], max_new_tokens=4))
    outputs = engine.run()
    np.testing.assert_array_equal(outputs[3], _static_reference(model, prompts[0], 4))


@pytest.mark.faults
def test_close_cancels_everything_and_refuses_new_work():
    from accelerate_tpu.serving import EngineClosed

    model = _model()
    rng = np.random.default_rng(16)
    engine = ContinuousBatcher(model, num_slots=1, max_length=64, chunk_size=2)
    prompt = rng.integers(1, 128, (4,)).astype(np.int32)
    engine.submit(Request(0, prompt, max_new_tokens=24))
    engine.submit(Request(1, prompt, max_new_tokens=4))
    engine.step()  # 0 in flight, 1 still queued
    results = engine.close()
    assert results[0].finish_reason == "cancelled" and results[0].tokens
    assert results[1].finish_reason == "cancelled" and not results[1].tokens
    assert engine.closed and not engine.pending
    with pytest.raises(EngineClosed):
        engine.submit(Request(2, prompt, max_new_tokens=4))
    assert engine.step() == []  # post-close step is a no-op
    assert engine.close() is results or engine.close() == results  # idempotent


@pytest.mark.faults
def test_drain_finishes_everything_then_reopens():
    model = _model()
    rng = np.random.default_rng(17)
    engine = ContinuousBatcher(model, num_slots=2, max_length=32, chunk_size=2)
    prompt = rng.integers(1, 128, (4,)).astype(np.int32)
    engine.submit(Request(0, prompt, max_new_tokens=4))
    results = engine.drain()
    assert results[0].finished and not engine.pending
    # drain is a flush, not a shutdown: the engine takes new work afterwards
    engine.submit(Request(1, prompt, max_new_tokens=4))
    outputs = engine.run()
    np.testing.assert_array_equal(outputs[1], _static_reference(model, prompt, 4))


@pytest.mark.faults
def test_mixed_adversarial_workload_engine_stays_up():
    """The acceptance-criterion mix: well-formed, oversized, deadline-expiring
    and cancelled requests together. Every well-formed request finishes with
    token-identical greedy output, the stats ledger accounts for every request,
    and the engine ends the run alive and empty."""
    model = _model()
    rng = np.random.default_rng(18)
    engine = ContinuousBatcher(model, num_slots=2, max_length=32, chunk_size=2)
    well_formed = {i: rng.integers(1, 128, (3 + i,)).astype(np.int32) for i in range(3)}
    for i, p in well_formed.items():
        engine.submit(Request(i, p, max_new_tokens=4))
    with pytest.raises(ValueError, match="slot capacity"):  # oversized: rejected synchronously
        engine.submit(Request(10, rng.integers(1, 128, (30,)).astype(np.int32), max_new_tokens=8))
    engine.submit(Request(11, well_formed[0], max_new_tokens=8, deadline_s=0.0))  # expires
    engine.submit(Request(12, well_formed[1], max_new_tokens=8))
    engine.cancel(12)  # cancelled while queued
    outputs = engine.run()
    for i, p in well_formed.items():
        np.testing.assert_array_equal(outputs[i], _static_reference(model, p, 4))
    assert engine.results[11].finish_reason == "timeout"
    assert engine.results[12].finish_reason == "cancelled"
    reasons = engine.stats["finish_reasons"]
    assert reasons["length"] == 3 and reasons["timeout"] == 1 and reasons["cancelled"] == 1
    assert sum(reasons.values()) == len(engine.results)
    assert engine.free_slots == engine.num_slots and not engine.pending and not engine.closed


@pytest.mark.serving_soak
def test_serving_soak_large_mixed_workload():
    """Soak: dozens of mixed requests through few slots; everything matches the
    static path and the decode program still compiled exactly once."""
    model = _model()
    rng = np.random.default_rng(7)
    engine = ContinuousBatcher(model, num_slots=4, max_length=64, chunk_size=8)
    requests = []
    for i in range(24):
        n = int(rng.integers(2, 24))
        m = int(rng.integers(2, 16))
        requests.append(
            Request(i, rng.integers(1, 128, (n,)).astype(np.int32), max_new_tokens=m)
        )
    outputs = engine.run(requests)
    assert engine.trace_counts["decode_chunk"] == 1
    for req in requests:
        np.testing.assert_array_equal(
            outputs[req.request_id],
            _static_reference(model, np.asarray(req.input_ids), req.max_new_tokens),
        )


# ------------------------------------------------------------- the slot mirror, alone

def test_what_the_mirror_pushed_keeps_its_values_when_the_mirror_is_written(monkeypatch):
    """ROADMAP D9: on a CPU `jnp.asarray(mirror)` may alias the numpy buffer
    (it does where the buffer is 64-byte aligned), which the host writes in
    place while a chunk that reads the pushed array runs. `_SlotMirror.operands()`
    pushes copies — of every operand it makes. Held against the worst backend:
    one whose `asarray` always aliases."""
    from accelerate_tpu import serving
    from accelerate_tpu.serving import _SlotMirror

    monkeypatch.setattr(serving.jnp, "asarray", lambda host: host)
    mirror = _SlotMirror(num_slots=2, pages_per_slot=3, history_length=8)
    mirror.admit(0, np.arange(1, 5, dtype=np.int32), budget=3, eos=7, temperature=0.5, penalty=1.25,
                 page_row=np.array([4, 5, 0], np.int32))
    host_only, update, history = mirror.operands()
    pushed = [*host_only, update, history]
    before = [np.array(x) for x in pushed]
    assert before[0][0] == 7 and before[3][0].tolist() == [4, 5, 0] and before[5][0, :4].tolist() == [1, 2, 3, 4]
    assert before[4].tolist() == [[1, 0], [1, 0], [4, 0], [1, 0], [3, 0]]  # changed, from_buffer, pos, active, rem
    for host in (mirror.eos, mirror.temp, mirror.pen, mirror.page_table, mirror.history, mirror.pos, mirror.rem):
        host[...] = 9
    mirror.changed[:] = mirror.from_buffer[:] = mirror.active[:] = False
    for then, now in zip(before, pushed):
        np.testing.assert_array_equal(np.asarray(now), then)
    # nothing changed by a slot operation since: the same device arrays, and the one array of zeros
    mirror.dispatched(steps=None)
    again, update, _ = mirror.operands()
    assert all(a is b for a, b in zip(again, host_only)) and not np.asarray(update).any()
