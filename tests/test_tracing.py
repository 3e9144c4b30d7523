"""Request-scoped tracing tests: span lifecycle/nesting semantics, the
flight-recorder ring (eviction order, streamed JSONL, touch-file dumps),
Perfetto trace-event export schema, hang-watchdog firing on a stalled fake
step, cross-process trace-id propagation through a real Supervisor child, the
serving engine's submit->finish span coverage, the goodput unaccounted-time
alarm, and the chaos smoke-serve dump carrying injected faults as events."""

import contextlib
import json
import os
import sys
import time

import numpy as np
import pytest

from accelerate_tpu.telemetry import (
    FlightRecorder,
    Tracer,
    collect_trace_dir,
    read_span_jsonl,
    to_trace_events,
)
from accelerate_tpu.telemetry.flight_recorder import DUMP_TOUCH_FILE
from accelerate_tpu.telemetry.tracing import TRACE_DIR_ENV, TRACE_ID_ENV, TRACE_PARENT_ENV

pytestmark = pytest.mark.tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ span semantics
def test_span_lifecycle_and_nesting():
    recorder = FlightRecorder()
    tracer = Tracer(recorder=recorder, category="test")
    with tracer.span("outer", a=1) as outer:
        assert tracer.current_span is outer
        outer.event("milestone", note="hi")
        with tracer.span("inner") as inner:
            assert inner.parent_id == outer.span_id
        assert tracer.current_span is outer
    assert tracer.current_span is None

    records = recorder.records()
    assert [r["name"] for r in records] == ["inner", "outer"]  # completion order
    outer_rec = records[1]
    assert outer_rec["attrs"] == {"a": 1}
    assert outer_rec["events"][0]["name"] == "milestone"
    assert outer_rec["trace_id"] == records[0]["trace_id"] == tracer.trace_id
    assert outer_rec["end_unix"] >= outer_rec["start_unix"]
    # idempotent end: a double-ended span records exactly once
    span = tracer.start_span("solo")
    span.end()
    span.end()
    assert [r["name"] for r in recorder.records()].count("solo") == 1


def test_span_error_annotation_and_propagation():
    tracer = Tracer(recorder=FlightRecorder())
    with pytest.raises(RuntimeError):
        with tracer.span("doomed"):
            raise RuntimeError("boom")
    (record,) = tracer.recorder.records()
    assert "boom" in record["attrs"]["error"]
    assert tracer.current_span is None  # the stack unwound


def test_annotation_host_value_gate():
    """The runtime half of TPU112: a device-array-shaped value (anything
    non-host) must raise before it can hide a blocking readback."""
    tracer = Tracer(recorder=FlightRecorder())
    with pytest.raises(TypeError, match="host values"):
        tracer.start_span("bad", payload=np.ones(3))
    span = tracer.start_span("ok", n=3, f=0.5, s="x", b=True, none=None)
    with pytest.raises(TypeError, match="host values"):
        span.event("bad", arr=[1, 2])
    span.end()


# ------------------------------------------------------------------ flight recorder
def test_ring_buffer_eviction_order():
    recorder = FlightRecorder(capacity=4)
    tracer = Tracer(recorder=recorder)
    for i in range(10):
        tracer.start_span("s", idx=i).end()
    records = recorder.records()
    assert len(records) == 4
    assert [r["attrs"]["idx"] for r in records] == [6, 7, 8, 9]  # oldest evicted first
    assert recorder.registry.value("trace_spans_recorded_total") == 10
    assert recorder.registry.value("trace_spans_evicted_total") == 6


def test_streamed_jsonl_survives_torn_tail(tmp_path):
    trace_dir = str(tmp_path / "trace")
    recorder = FlightRecorder(log_dir=trace_dir)
    tracer = Tracer(recorder=recorder)
    open_span = tracer.start_span("unfinished")  # streamed as span_start only
    tracer.start_span("done").end()
    tracer.event("marker", k=1)
    stream = os.path.join(trace_dir, f"spans_{os.getpid()}.jsonl")
    with open(stream, "a") as f:
        f.write('{"kind": "span", "name": "torn')  # a killed writer's last line
    records = read_span_jsonl(stream)
    kinds = {(r["kind"], r["name"]) for r in records}
    assert ("span_start", "unfinished") in kinds
    assert ("span", "done") in kinds
    assert ("event", "marker") in kinds
    assert not any(r.get("name") == "torn" for r in records)
    assert collect_trace_dir(trace_dir) == sorted(
        records, key=lambda r: r.get("start_unix", r.get("t_unix", 0.0))
    )
    open_span.end()


def test_perfetto_export_schema_and_roundtrip(tmp_path):
    trace_dir = str(tmp_path / "trace")
    recorder = FlightRecorder(log_dir=trace_dir)
    tracer = Tracer(recorder=recorder)
    with tracer.span("parent", kindof="serve") as parent:
        parent.event("instant", x=1)
        with tracer.span("child"):
            pass
    tracer.event("standalone")
    dangling = tracer.start_span("dangling")  # never ended: only span_start streams

    path = recorder.dump(reason="test")
    data = json.loads(open(path).read())
    assert set(data) == {"traceEvents", "displayTimeUnit"}
    events = data["traceEvents"]
    phases = {e["ph"] for e in events}
    assert phases <= {"M", "X", "B", "i"}
    for event in events:
        assert isinstance(event["ts"], int) if event["ph"] != "M" else True
        assert "pid" in event and "name" in event
        if event["ph"] == "X":
            assert event["dur"] >= 0
    # monotonic per-pid ordering (what makes the timeline readable)
    ts = [e["ts"] for e in events if e["ph"] != "M"]
    assert ts == sorted(ts)
    by_name = {e["name"] for e in events}
    assert {"parent", "child", "instant", "standalone"} <= by_name
    # the dangling span is not in the RING dump (it never completed)...
    assert "dangling" not in by_name
    # ...but its streamed span_start exports as an unfinished "B" event.
    stitched = to_trace_events(collect_trace_dir(trace_dir))["traceEvents"]
    assert any(e["name"] == "dangling" and e["ph"] == "B" for e in stitched)
    dangling.end()


def test_touch_file_dump_trigger(tmp_path):
    trace_dir = str(tmp_path / "trace")
    recorder = FlightRecorder(log_dir=trace_dir, poll_every=2)
    Tracer(recorder=recorder).start_span("work").end()
    touch = os.path.join(trace_dir, DUMP_TOUCH_FILE)
    open(touch, "w").close()
    assert recorder.poll() is False  # off-cadence call: no probe yet
    assert recorder.poll() is True  # cadence hit: trigger consumed, dump written
    assert not os.path.exists(touch)
    dumps = [n for n in os.listdir(trace_dir) if n.startswith("trace_") and n.endswith(".json")]
    assert len(dumps) == 1


# ------------------------------------------------------------------ hang watchdog
def test_hang_watchdog_fires_on_stalled_fake_step(tmp_path):
    from accelerate_tpu.chaos.injectors import FakeClock

    clock = FakeClock()
    trace_dir = str(tmp_path / "trace")
    recorder = FlightRecorder(log_dir=trace_dir, clock=clock.monotonic)
    tracer = Tracer(recorder=recorder, clock=clock.monotonic)
    watchdog = recorder.start_watchdog(
        deadline_s=30.0, tracer=tracer, clock=clock.monotonic, start_thread=False
    )
    clock.sleep(100)
    assert watchdog.check_once() is False  # unarmed: warmup is not a stall
    tracer.start_span("train.step", step=0).end()
    recorder.heartbeat()
    clock.sleep(10)
    assert watchdog.check_once() is False  # within deadline

    clock.sleep(25)  # 35s since the last heartbeat: the step stalled
    assert watchdog.check_once() is True
    assert watchdog.check_once() is False  # one artifact per stall, not per poll

    # The dump carries the hang marker + the step that preceded the stall...
    data = json.loads(open(watchdog.last_dump).read())
    names = {e["name"] for e in data["traceEvents"]}
    assert "hang.detected" in names and "train.step" in names
    # ...and the stacks file shows what every thread was doing.
    stacks = open(watchdog.last_stacks_path).read()
    assert "thread" in stacks and "test_hang_watchdog_fires_on_stalled_fake_step" in stacks

    recorder.heartbeat()  # the loop came back: the watchdog re-arms
    clock.sleep(31)
    assert watchdog.check_once() is True
    assert watchdog.fired_count == 2


# ------------------------------------------------------------------ cross-process
def test_trace_context_propagates_through_real_supervisor_child(tmp_path):
    from accelerate_tpu.fault_tolerance import Supervisor

    trace_dir = str(tmp_path / "trace")
    tracer = Tracer(recorder=FlightRecorder(log_dir=trace_dir), category="supervisor")
    child_src = (
        "from accelerate_tpu.telemetry.tracing import Tracer\n"
        "tracer = Tracer.from_env()\n"
        "with tracer.span('child.work', category='worker'):\n"
        "    pass\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    supervisor = Supervisor([sys.executable, "-c", child_src], env=env, tracer=tracer)
    assert supervisor.run() == 0

    records = collect_trace_dir(trace_dir)
    attempts = [r for r in records if r["name"] == "supervisor.attempt" and r["kind"] == "span"]
    child_spans = [r for r in records if r["name"] == "child.work" and r["kind"] == "span"]
    assert len(attempts) == 1 and len(child_spans) == 1
    # One trace id across both processes; the child's root span parents under
    # the supervisor attempt that spawned it.
    assert child_spans[0]["trace_id"] == attempts[0]["trace_id"] == tracer.trace_id
    assert child_spans[0]["parent_id"] == attempts[0]["span_id"]
    assert child_spans[0]["pid"] != attempts[0]["pid"]
    exits = [r for r in records if r["name"] == "supervisor.child_exit"]
    assert exits and exits[0]["attrs"]["exit_code"] == 0


def test_tracer_from_env_reads_protocol(tmp_path, monkeypatch):
    monkeypatch.setenv(TRACE_DIR_ENV, str(tmp_path / "t"))
    monkeypatch.setenv(TRACE_ID_ENV, "cafecafecafe")
    monkeypatch.setenv(TRACE_PARENT_ENV, "beefbeefbeef")
    tracer = Tracer.from_env()
    assert tracer.trace_id == "cafecafecafe"
    assert tracer.root_parent_id == "beefbeefbeef"
    assert tracer.recorder.log_dir == str(tmp_path / "t")
    span = tracer.start_span("root")
    assert span.parent_id == "beefbeefbeef"
    span.end()
    # inject_env round-trips the context for the next hop down
    env = tracer.inject_env({})
    assert env[TRACE_ID_ENV] == "cafecafecafe"
    assert env[TRACE_DIR_ENV] == str(tmp_path / "t")


# ------------------------------------------------------------------ serving spans
def _tiny_llama():
    from accelerate_tpu.models.llama import LlamaConfig, create_llama_model

    cfg = LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=64,
        rope_theta=10000.0,
    )
    return create_llama_model(cfg, seq_len=32)


def test_serving_request_lifecycle_spans():
    from accelerate_tpu.serving import ContinuousBatcher, Request

    recorder = FlightRecorder()
    tracer = Tracer(recorder=recorder, category="serve")
    engine = ContinuousBatcher(_tiny_llama(), num_slots=2, max_length=64, chunk_size=4,
                               tracer=tracer)
    rng = np.random.default_rng(0)
    for i in range(4):
        engine.submit(Request(i, rng.integers(1, 128, (6,)).astype(np.int32), max_new_tokens=5))
    engine.run()
    engine.close()

    records = recorder.records()
    requests = {r["attrs"]["request_id"]: r for r in records if r["name"] == "serve.request"}
    assert sorted(requests) == [0, 1, 2, 3]
    for record in requests.values():
        assert record["attrs"]["finish_reason"] == "length"
        assert record["attrs"]["tokens"] == 5
        assert [e["name"] for e in record["events"]] == [
            "submitted", "admitted", "first_token", "handed_back"]
        admitted = record["events"][1]["attrs"]
        assert admitted["queue_wait_s"] >= 0 and "pages_reserved" in admitted
    inserts = [r for r in records if r["name"] == "serve.insert"]
    assert sorted(r["attrs"]["request_id"] for r in inserts) == [0, 1, 2, 3]
    chunks = [r for r in records if r["name"] == "serve.decode_chunk"]
    assert chunks and all(r["attrs"]["active_slots"] >= 1 for r in chunks)


@pytest.mark.parametrize("backlog", [False, True], ids=["no-backlog", "backlog"])
def test_serving_step_span_tree_and_hand_back(backlog):
    """One `serve.step` a step() over its inserts and its decode chunk; the
    step's host and device-wait seconds add up to it, and it waits once
    (`waits`), after everything it dispatches (`dispatched_ahead`): an insert
    is dispatch only, with no `serve.insert.wait` under it; every request is
    handed back once, its first token on the host only since a step's drain
    (`held_s`), and TTFT is observed there (a one-token request and one that
    ends in its first chunk included: their spans wait for the event).

    With an empty queue the tree is what it was: nothing is in flight when a
    step returns (`in_flight_at_return` 0), no chunk is `ahead`, and a chunk's
    span lies inside the step that dispatched it. With a backlog the chunk's
    span — still ONE record a chunk, opened at its dispatch, a child of the
    step that dispatched it — ends in the NEXT step, at its readback."""
    from accelerate_tpu.serving import ContinuousBatcher, Request

    from test_serving import _serve  # all at once, or each request when a slot is free for it

    recorder = FlightRecorder()
    tracer = Tracer(recorder=recorder, category="serve")
    opened, open_span = [], tracer.span
    tracer.span = lambda name, **kwargs: (opened.append(name), open_span(name, **kwargs))[1]
    engine = ContinuousBatcher(_tiny_llama(), num_slots=2, max_length=64, chunk_size=4,
                               tracer=tracer)
    rng = np.random.default_rng(1)
    lengths = [9, 1, 3, 6, 12]
    stepped = len(_serve(engine, [
        Request(i, rng.integers(1, 128, (6,)).astype(np.int32), max_new_tokens=n)
        for i, n in enumerate(lengths)], backlog))
    results = dict(engine.results)
    stats = engine.stats
    engine.close()

    records = recorder.records()
    steps = [r for r in records if r["name"] == "serve.step"]
    assert len(steps) == stepped
    by_parent = {}
    for r in records:
        if r["name"] in ("serve.insert", "serve.decode_chunk"):
            by_parent.setdefault(r["parent_id"], []).append(r)
    assert set(by_parent) <= {s["span_id"] for s in steps}  # nothing outside a step
    assert all(s["parent_id"] is None for s in steps)
    in_flight = 0  # chunks the step before left running
    for step in steps:
        attrs = step["attrs"]
        children = by_parent.get(step["span_id"], [])
        inserts = [c for c in children if c["name"] == "serve.insert"]
        chunks = [c for c in children if c["name"] == "serve.decode_chunk"]
        assert len(inserts) == attrs["inserts"] and len(chunks) <= 1
        # (the span ends a moment after it is annotated: a millisecond of room under a loaded host)
        assert attrs["host_s"] + attrs["device_wait_s"] == pytest.approx(step["duration_s"], abs=1e-3)
        assert (attrs["admit_s"] + attrs["push_s"] + attrs["dispatch_s"] + attrs["device_wait_s"]
                + attrs["drain_s"]) <= step["duration_s"] + 2e-4
        assert 0.0 <= attrs["starved_s"] <= attrs["host_s"] + 1e-5  # empty-handed only on its own time
        assert all("device_wait_s" not in c["attrs"] for c in inserts)  # dispatch only
        assert attrs["dispatched_ahead"] == len(inserts) + len(chunks)
        # one wait whenever there is something to read back: an older chunk, or this step's own work
        leaves_one = attrs["in_flight_at_return"]
        assert attrs["waits"] == (1 if in_flight or (children and not leaves_one) else 0)
        assert [c["attrs"]["ahead"] for c in chunks] == [bool(in_flight)] * len(chunks)
        for child in children:
            assert step["start_unix"] <= child["start_unix"]
            if child["name"] == "serve.insert" or not leaves_one:
                assert child["end_unix"] <= step["end_unix"]
            else:  # left in flight: read back, and ended, by a later step
                assert child["end_unix"] > step["end_unix"]
        in_flight = leaves_one
    assert in_flight == 0 and max(s["attrs"]["in_flight_at_return"] for s in steps) == int(backlog)
    assert sum(s["attrs"]["inserts"] for s in steps) == len(lengths)
    chunks = [r for r in records if r["name"] == "serve.decode_chunk"]
    assert len(chunks) == stats["chunks"]  # one record a chunk
    assert stats["chunks_ahead_share"] == pytest.approx(
        sum(c["attrs"]["ahead"] for c in chunks) / len(chunks))
    assert (stats["chunks_ahead_share"] > 0) is backlog
    assert not [r for r in records if r["name"] in (
        "serve.admit", "serve.insert.wait", "serve.chunk.push", "serve.chunk.dispatch",
        "serve.chunk.wait", "serve.first_tokens.wait", "serve.drain")]  # annotations only
    # the tree of a step: no wait under an insert, one wait a step (`serve.decode_chunk` outlives a
    # call frame under a backlog, so it is opened by `start_span`, as `serve.request` is)
    assert set(opened) == {"serve.step", "serve.admit", "serve.insert",
                           "serve.chunk.push", "serve.chunk.dispatch", "serve.chunk.wait",
                           "serve.drain"}
    assert opened.count("serve.chunk.wait") == len([s for s in steps if s["attrs"]["waits"]])
    assert stats["waits_per_step"] == 1.0

    requests = {r["attrs"]["request_id"]: r for r in records if r["name"] == "serve.request"}
    ttft_sum = 0.0
    for rid, n in enumerate(lengths):
        events = {e["name"]: e for e in requests[rid]["events"]}
        assert [e["name"] for e in requests[rid]["events"]].count("handed_back") == 1
        handed = events["handed_back"]["attrs"]
        assert handed["held_s"] >= 0 and handed["ttft_s"] >= handed["held_s"]
        # the token reaches the host as a step drains: held for part of that drain, no chunk
        assert handed["held_s"] <= max(s["attrs"]["drain_s"] for s in steps) + 1e-4
        assert events["admitted"]["attrs"]["queue_wait_s"] <= handed["ttft_s"]
        assert requests[rid]["attrs"]["tokens"] == n
        ttft_sum += handed["ttft_s"]
        result = results[rid]
        assert result.submit_time <= result.first_token_time <= result.finish_time
        # the token was on the host `held_s` before the step returned
        assert handed["ttft_s"] == pytest.approx(
            result.first_token_time - result.submit_time + handed["held_s"], abs=1e-5)
    ttft = engine.metrics.get("serving_ttft_seconds")
    assert ttft.count == len(lengths)
    assert ttft.sum == pytest.approx(ttft_sum, abs=1e-9)


@pytest.mark.parametrize("family", ["gpt-neox-tiny", "latent-moe-tiny", "olmo-hybrid-tiny", "falcon-h1-tiny"])
def test_decode_chunk_span_carries_what_the_benchmarks_readers_take(family):
    """`serve.decode_chunk` is one record a chunk, starts at its dispatch, and
    carries — running ahead or not — every attribute `chipbench/chunk_counters.py`
    and the roofline readers ask of it: the host's counts as it is dispatched,
    the readback's as it is ended. The run switches from running ahead (a
    backlog) to not (the queue runs dry) and back: one chunk program, compiled
    once; the insert's signature is the parent's."""
    import inspect

    from accelerate_tpu.models import create_named_model
    from accelerate_tpu.serving import ContinuousBatcher, Request

    needs = {"chunk_size", "active_slots", "pages_in_use", "live_pages", "window_pages", "read_blocks",
             "kv_row_values", "tokens_streamed", "ahead"}
    needs |= {"latent-moe-tiny": {"expert_tokens_max", "expert_tokens_mean", "experts_touched"},
              "olmo-hybrid-tiny": {"state_slots", "state_bytes_per_slot", "kv_page_bytes"},
              "falcon-h1-tiny": {"state_slots", "state_bytes_per_slot", "kv_page_bytes"}}.get(family, set())
    model = create_named_model(family)
    recorder = FlightRecorder()
    tracer = Tracer(recorder=recorder, category="serve")
    engine = ContinuousBatcher(model, num_slots=2, max_length=48, chunk_size=3, page_size=8, tracer=tracer)
    rng = np.random.default_rng(7)
    vocab = model.module.config.vocab_size

    def wave(first_id):
        for i in range(first_id, first_id + 4):
            engine.submit(Request(i, rng.integers(1, vocab, (5 + i,)).astype(np.int32), max_new_tokens=7))
        dispatched = []
        while engine.pending:
            before = tracer.now()
            engine.step()
            dispatched.append((before, tracer.now()))
        return dispatched

    windows = wave(0) + wave(10)  # a backlog, the queue dry, a backlog again
    chunks = [r for r in recorder.records() if r["name"] == "serve.decode_chunk"]
    steps = [r for r in recorder.records() if r["name"] == "serve.step"]
    assert len(chunks) == engine.stats["chunks"] and engine.trace_counts["decode_chunk"] == 1
    assert engine._chunk_fn._cache_size() == 1
    ahead = [c["attrs"]["ahead"] for c in chunks]
    assert True in ahead and False in ahead and ahead[0] is False
    assert {s["attrs"]["in_flight_at_return"] for s in steps} == {0, 1}
    assert engine.stats["chunks_ahead_share"] == pytest.approx(sum(ahead) / len(ahead))
    assert engine.metrics.get("serving_chunks_ahead_share").value == engine.stats["chunks_ahead_share"]
    by_id = {s["span_id"]: s for s in steps}
    for chunk in chunks:
        assert needs <= set(chunk["attrs"]), needs - set(chunk["attrs"])
        step = by_id[chunk["parent_id"]]  # the step that dispatched it: the span starts inside it
        assert step["start_unix"] <= chunk["start_unix"] <= step["end_unix"]
        assert chunk["end_unix"] > chunk["start_unix"] and chunk["attrs"]["tokens_streamed"] >= 1
        assert sum(a <= chunk["start_unix"] <= b for a, b in windows) == 1
    insert = inspect.signature(engine._insert_fn(8).__wrapped__)
    assert list(insert.parameters) == [
        "params", "pool_cache", "presence", "suffix_ids", "real_len", "matched_len", "matched_pages",
        "page_row", "slot", "temperature", "penalty", "rng", "first_token"]
    engine.close()


def test_engine_ttft_is_the_routers_on_one_replica():
    """Both histograms are observed where the first token is handed back, so
    on one replica they agree to well within one insert (they used to differ
    by a decode chunk)."""
    from accelerate_tpu.router import Router
    from accelerate_tpu.serving import Request

    recorder = FlightRecorder()
    tracer = Tracer(recorder=recorder, category="serve")
    router = Router(_tiny_llama(), replicas=1, num_slots=2, max_length=64, chunk_size=4,
                    tracer=tracer)
    rng = np.random.default_rng(2)
    for i in range(5):
        router.submit(Request(i, rng.integers(1, 128, (6,)).astype(np.int32), max_new_tokens=6))
    router.drain()
    engine = router.replica_set.replicas[0].engine
    mine = engine.metrics.get("serving_ttft_seconds")
    routers = router.metrics.get("serving_ttft_seconds")
    records = recorder.records()
    router.close()
    assert mine.count == routers.count == 5
    inserts = [r["duration_s"] for r in records if r["name"] == "serve.insert"]
    chunks = [r["duration_s"] for r in records if r["name"] == "serve.decode_chunk"]
    assert abs(routers.sum - mine.sum) / 5 < min(min(inserts), min(chunks))


# ------------------------------------------------------------------ profiler clock
def test_scoped_span_enters_a_profiler_annotation_of_its_name(monkeypatch):
    import jax

    entered, exited = [], []

    class Annotation:
        def __init__(self, name, **kwargs):
            assert not kwargs  # the name alone: attributes stay in the span
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            exited.append(self.name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    recorder = FlightRecorder()
    tracer = Tracer(recorder=recorder)
    with tracer.span("outer", a=1) as outer:
        with tracer.span("quiet", record=False) as quiet:
            with tracer.span("inner") as inner:
                assert inner.parent_id == outer.span_id  # the nearest recorded span
        assert quiet.duration_s >= inner.duration_s > 0
    tracer.start_span("lifecycle").end()  # unscoped: no annotation
    assert entered == ["outer", "quiet", "inner"]
    assert exited == ["inner", "quiet", "outer"]
    assert [r["name"] for r in recorder.records()] == ["inner", "outer", "lifecycle"]
    with pytest.raises(RuntimeError):
        with tracer.span("doomed"):
            raise RuntimeError("boom")
    assert exited[-1] == "doomed"

    # now(): the timeline of the records, for mapping another clock onto it
    before = tracer.now()
    with tracer.span("timed"):
        pass
    record = recorder.records()[-1]
    assert before <= record["start_unix"] <= record["end_unix"] <= tracer.now()


def test_tracing_runs_where_jax_is_not_loaded():
    """`tracing.py`, the flight recorder and the metrics are stdlib alone: a
    scoped span works, without its annotation, in a process that never loads
    jax. (The package's parent imports jax, so the three are loaded here under
    a bare stand-in for it.)"""
    import subprocess

    code = f"""
import logging, os, sys, types
root = {os.path.join(REPO, "accelerate_tpu")!r}
for name, path in (("accelerate_tpu", root), ("accelerate_tpu.telemetry", os.path.join(root, "telemetry"))):
    package = types.ModuleType(name)
    package.__path__ = [path]
    sys.modules[name] = package
stand_in = types.ModuleType("accelerate_tpu.logging")
stand_in.get_logger = logging.getLogger
sys.modules["accelerate_tpu.logging"] = stand_in
from accelerate_tpu.telemetry.tracing import Tracer
tracer = Tracer()
with tracer.span("outer"):
    with tracer.span("quiet", record=False):
        pass
assert [r["name"] for r in tracer.recorder.records()] == ["outer"]
assert "jax" not in sys.modules and "numpy" not in sys.modules
"""
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]


# ------------------------------------------------------------------ device scopes
def _has_scope(program_text: str, scope: str) -> bool:
    """`scope` as a whole component of some location's name path (the text
    nests a loop body's locations, so a path may start or end at it)."""
    import re

    return re.search(r'loc\("([^"]*/)?' + re.escape(scope) + r'(/[^"]*)?"', program_text) is not None


def test_decode_chunk_and_insert_programs_carry_named_scopes():
    """The device side of a capture reads by phase: the scope names are in
    the programs' debug locations (flax names the layers, these the rest)."""
    from accelerate_tpu.serving import ContinuousBatcher, Request

    engine = ContinuousBatcher(_tiny_llama(), num_slots=2, max_length=64, chunk_size=4,
                               tracer=Tracer(recorder=FlightRecorder()))
    text = engine.lower_decode_chunk().as_text(debug_info=True)
    for scope in ("kv_write", "kv_read", "sample", "pack_stream"):
        assert _has_scope(text, scope), scope

    engine.submit(Request(0, np.arange(1, 7, dtype=np.int32), max_new_tokens=2))
    engine.run()
    (bucket, insert), = engine._insert_fns.items()
    lowered = insert.lower(
        engine.params, engine._cache, engine._presence, np.zeros((1, bucket), np.int32),
        np.int32(6), np.int32(0), np.int32(0), np.zeros((engine.pages_per_slot,), np.int32),
        np.int32(0), np.float32(1.0), np.float32(1.0), engine._rng, engine._first_token)
    text = lowered.as_text(debug_info=True)
    for scope in ("kv_read", "kv_write", "sample"):
        assert _has_scope(text, scope), scope
    engine.close()


def _regression_job(accelerator, n_train=32, n_eval=None):
    """A prepared regression model, optimizer and loaders of batch 8."""
    import optax

    from accelerate_tpu import SimpleDataLoader
    from accelerate_tpu.data_loader import BatchSampler

    from test_training import make_regression_data, make_regression_model

    loaders = [
        SimpleDataLoader(data, BatchSampler(range(len(data)), 8))
        for data in (make_regression_data(n=n) for n in (n_train, n_eval) if n)
    ]
    return accelerator.prepare(make_regression_model(seed=0), optax.sgd(0.05), *loaders)


def test_fused_step_carries_named_scopes_and_the_loader_feeds_data_wait(monkeypatch):
    """`forward_backward`, `clip` and `optimizer_update` are in the fused
    step's program; the prepared loader stamps each wait for a batch under a
    `train.data_wait` annotation, and `train_step()` folds the stamp of its
    batch into the timeline's "data_wait" phase, beside `train.step`."""
    import jax

    from accelerate_tpu import Accelerator

    entered = []

    def annotation(name):
        entered.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)
    recorder = FlightRecorder()
    accelerator = Accelerator(tracer=Tracer(recorder=recorder, category="train"))
    pmodel, popt, ploader = _regression_job(accelerator)
    step_fn = accelerator.train_step(max_grad_norm=1.0)
    for _epoch in range(2):
        for batch in ploader:
            assert ploader.data_wait_s > 0
            step_fn(batch)
            assert ploader.data_wait_s is None  # taken once

    fused = step_fn.__wrapped__
    while not hasattr(fused, "_jitted"):
        fused = fused.__wrapped__
    (program,) = fused._jitted.values()
    text = program.lower(pmodel.params, popt.opt_state, *fused._scalar_bufs, batch).as_text(debug_info=True)
    for scope in ("forward_backward", "clip", "optimizer_update"):
        assert _has_scope(text, scope), scope

    report = accelerator.timeline.goodput()
    assert report["steps"] == 8
    assert set(report["phase_s"]) >= {"data_wait", "dispatch"}
    registry = accelerator.telemetry
    for phase in ("data_wait", "dispatch", "step"):
        assert registry.get(f"train_{phase}_seconds").count == 8, phase
    # in a capture: a wait a `next()` (the one that ends a pass too), a step a call
    assert entered.count("train.data_wait") == 10 and entered.count("train.step") == 8
    # in the ring: the steps alone
    names = [r["name"] for r in recorder.records()]
    assert names.count("train.step") == 8 and "train.data_wait" not in names


def test_an_evaluation_pass_is_nobodys_train_step():
    """A prepared loader opens no step: a pass that feeds no `train_step()`
    stays out of the productive time and of the "data_wait" phase, and shows
    as unaccounted time."""
    from accelerate_tpu import Accelerator

    accelerator = Accelerator(tracer=Tracer(recorder=FlightRecorder(), category="train"))
    _, _, train_loader, eval_loader = _regression_job(accelerator, n_eval=24)
    step_fn = accelerator.train_step()
    for batch in train_loader:  # compiles
        step_fn(batch)
    timeline = accelerator.timeline
    timeline.reset()
    waits_before = accelerator.telemetry.get("train_data_wait_seconds").count

    for batch in train_loader:
        step_fn(batch)
    t0 = time.perf_counter()
    for _batch in eval_loader:
        time.sleep(0.05)
    eval_s = time.perf_counter() - t0
    for batch in train_loader:
        step_fn(batch)

    report = timeline.goodput()
    assert report["steps"] == 8 and eval_s >= 0.15
    assert timeline._step_open_since is None
    assert report["productive_s"] + eval_s <= report["total_s"]
    assert report["unaccounted_s"] >= eval_s - 1e-3
    assert accelerator.telemetry.get("train_data_wait_seconds").count - waits_before == 8


# ------------------------------------------------------------------ goodput alarm
def test_goodput_unaccounted_warning_and_span_event():
    from accelerate_tpu.chaos.injectors import FakeClock
    from accelerate_tpu.telemetry import StepTimeline

    clock = FakeClock()
    tracer = Tracer(recorder=FlightRecorder(), clock=clock.monotonic)
    timeline = StepTimeline(
        clock=clock.perf_counter, tracer=tracer, unaccounted_warn_s=50.0
    )
    with timeline.phase("dispatch"):
        clock.sleep(1.0)
    timeline.step_done()
    clock.sleep(100.0)  # an opaque stall: nothing productive, nothing charged
    report = timeline.goodput()
    assert report["unaccounted_s"] >= 50.0
    events = [r for r in tracer.recorder.records() if r["name"] == "goodput.unaccounted"]
    assert len(events) == 1
    assert events[0]["attrs"]["unaccounted_s"] == pytest.approx(report["unaccounted_s"], abs=0.1)

    timeline.goodput()  # once per window, not per call
    assert len([r for r in tracer.recorder.records() if r["name"] == "goodput.unaccounted"]) == 1
    timeline.reset()
    clock.sleep(200.0)
    timeline.goodput()  # a fresh window re-arms the alarm
    assert len([r for r in tracer.recorder.records() if r["name"] == "goodput.unaccounted"]) == 2


# ------------------------------------------------------------------ chaos dump
@pytest.mark.chaos
def test_chaos_smoke_serve_dump_is_perfetto_complete(tmp_path):
    """The acceptance path: `chaos run smoke-serve` with a trace dir, then
    `trace dump` — the JSON must hold submit->finish spans for every request
    and every injected fault as an event."""
    from accelerate_tpu.chaos import ChaosRunner, builtin_plans
    from accelerate_tpu.commands.trace import trace_dump_command

    trace_dir = str(tmp_path / "trace")
    runner = ChaosRunner(builtin_plans()["smoke-serve"], trace_dir=trace_dir)
    report = runner.run_serve(num_requests=6)
    assert report.ok, report.render_text()
    trace_check = next(c for c in report.checks if c.name == "trace_complete")
    assert trace_check.passed and trace_check.details["request_spans"] >= 6

    class Args:
        pass

    args = Args()
    args.trace_dir, args.out, args.wait = trace_dir, None, 0.0
    with pytest.raises(SystemExit) as exc:
        trace_dump_command(args)
    assert exc.value.code == 0
    data = json.loads(open(os.path.join(trace_dir, "trace.json")).read())
    names = [e["name"] for e in data["traceEvents"]]
    finished = [
        e for e in data["traceEvents"]
        if e["name"] == "serve.request" and "finish_reason" in e.get("args", {})
    ]
    assert len(finished) == trace_check.details["accepted"]
    for kind in ("serve.dispatch_stall", "serve.queue_burst", "serve.dispatch_error"):
        assert f"chaos.{kind}" in names  # the injected faults, on the timeline
    assert "serve.blast_radius" in names  # the dispatch failure's blast radius


def test_trace_export_cli_stitches_multiple_streams(tmp_path):
    from accelerate_tpu.commands.trace import trace_export_command

    trace_dir = str(tmp_path / "trace")
    recorder = FlightRecorder(log_dir=trace_dir)
    Tracer(recorder=recorder, trace_id="feedfacefeed").start_span("a").end()
    # a second "process": same dir, different stream file
    other = os.path.join(trace_dir, "spans_99999.jsonl")
    with open(other, "w") as f:
        f.write(json.dumps({
            "kind": "span", "name": "b", "cat": "x", "trace_id": "feedfacefeed",
            "span_id": "0b", "parent_id": None, "pid": 99999, "tid": 1,
            "start_unix": 1.0, "end_unix": 2.0, "duration_s": 1.0, "attrs": {},
        }) + "\n")

    class Args:
        pass

    args = Args()
    args.inputs, args.out = [trace_dir], str(tmp_path / "out.json")
    with pytest.raises(SystemExit) as exc:
        trace_export_command(args)
    assert exc.value.code == 0
    data = json.loads(open(args.out).read())
    pids = {e["pid"] for e in data["traceEvents"] if e["ph"] == "X"}
    assert len(pids) == 2
