"""The readers whose source is `program_span`, on the tiny cells of
conftest.py: each reads a number from the engine's own spans, the numbers fit
inside what the client loop timed from outside, and a ring that has wrapped is
an error and not a short window. No number here is a speed."""

import time

import pytest

from accelerate_tpu.telemetry import FlightRecorder, Tracer, set_default_tracer
from chipbench import harness, program_spans

READERS = ("first_token_held_ms", "queue_wait_ms", "insert_wall_ms", "step_host_ms.serve",
           "step_host_ms.open")


@pytest.fixture()
def tracer():
    """A process-wide tracer of the test's own (the Router takes it when
    handed none), so the ring holds this run alone."""
    mine = Tracer(recorder=FlightRecorder())
    previous = set_default_tracer(mine)
    yield mine
    set_default_tracer(previous)


def _serve(root, ledger, workload, seed, seconds=1.5):
    cell = harness.Cell(workload, root)
    driver = harness.load_module("drivers", cell.spec["driver"], root)
    out = driver.serve_once(cell, seed, seconds, harness.TraceWindow(False, 0.0, 0.0), ledger,
                            time.perf_counter())
    window = out["context"]["window"]
    # as a traced run leaves it: a capture that stopped a little into the window
    out["context"]["trace_span"] = (window["t0"], window["t0"] + 0.2)
    return cell, out


# A backlog keeps the engine's queue full, so a request submitted inside the
# short window may still be queued when it closes (on a slow machine all are):
# there the readers of per-request events may find nothing, as in the
# benchmark, which asks only `step_host_ms.serve` of its backlog cell.
@pytest.mark.parametrize("workload,must_read", [
    ("neox-tiny.tiny-backlog", ("insert_wall_ms", "step_host_ms.serve", "step_host_ms.open")),
    ("neox-tiny.tiny-open", READERS),
])
def test_each_reader_reads_a_number_that_fits_inside_the_clients(tiny_root, ledger, tracer, workload, must_read):
    cell, out = _serve(tiny_root, ledger, workload, seed=2**31 + 24)
    context, window = out["context"], out["context"]["window"]
    assert out["correct"]
    values = {name: harness.load_reader(name, cell.root).read(context) for name in READERS}
    assert all(isinstance(values[name], float) for name in must_read), values
    assert all(v is None or v >= 0.0 for v in values.values()), values
    assert values["step_host_ms.serve"] == values["step_host_ms.open"] > 0.0
    assert values["insert_wall_ms"] > 0.0
    after, before = program_spans.bounds(context)
    assert after == window["t0"] + 0.2 and before == window["t1"]

    # every step's own time is inside the wall the client timed around router.step()
    shift = tracer.now() - time.perf_counter()
    steps = program_spans.spans(context, "serve.step")
    walls = [(a, b) for a, b, *_ in window["steps"]]
    assert steps and len(steps) <= len([w for w in walls if after <= w[1]])
    for record in steps:
        start = record["start_unix"] - shift
        wall = next((b - a for a, b in walls if a - 1e-3 <= start <= b), None)
        assert wall is not None, "an engine step outside every client step"
        assert record["attrs"]["host_s"] <= record["duration_s"] + 2e-4 <= wall + 4e-4

    # every request: what the engine's spans say of its first token adds up to
    # no more than the client waited for it
    records = tracer.recorder.records()
    engine_id_of = {e["attrs"]["engine_id"]: r["attrs"]["request_id"]
                    for r in records if r["name"] == "serve.route"
                    for e in r.get("events", ()) if "engine_id" in e["attrs"]}
    insert_s = {r["attrs"]["request_id"]: r["duration_s"] for r in records if r["name"] == "serve.insert"}
    checked = 0
    for record in records:
        if record["name"] != "serve.request":
            continue
        events = {e["name"]: e["attrs"] for e in record["events"]}
        client = window["served"].get(engine_id_of.get(record["attrs"]["request_id"]))
        if client is None or client.first is None or "handed_back" not in events:
            continue  # a warm-up request, or one the window's close cancelled
        engine_side = (events["admitted"]["queue_wait_s"] + insert_s[record["attrs"]["request_id"]]
                       + events["handed_back"]["held_s"])
        assert engine_side <= events["handed_back"]["ttft_s"] + 1e-4
        # the client's own TTFT runs from when the request was due (before the
        # engine's submit()) to when router.step() had returned (after the hand-back)
        assert events["handed_back"]["ttft_s"] <= (client.first - client.due) + 1e-4
        checked += 1
    assert checked >= 5


def test_a_wrapped_ring_raises_and_a_program_without_the_clock_reads_nothing(tiny_root, ledger):
    small = Tracer(recorder=FlightRecorder(capacity=16))
    previous = set_default_tracer(small)
    try:
        cell, out = _serve(tiny_root, ledger, "neox-tiny.tiny-backlog", seed=7)
        context = out["context"]
        assert len(small.recorder.records()) == 16
        for name in ("serve.step", "serve.insert", "serve.request"):
            with pytest.raises(RuntimeError, match="wrapped"):
                program_spans.spans(context, name)
        with pytest.raises(RuntimeError, match="wrapped"):
            harness.load_reader("first_token_held_ms", cell.root).read(context)

        # a window the ring still covers from before its start reads, full or not
        late = dict(context, trace_span=(None, None),
                    window=dict(context["window"], t0=time.perf_counter(), t1=time.perf_counter() + 1.0))
        assert program_spans.spans(late, "serve.step") == []
    finally:
        set_default_tracer(previous)

    # the parent commit's tracer has no now(): nothing to read, and no error
    class Older:
        recorder = small.recorder

    previous = set_default_tracer(Older())
    try:
        assert program_spans.tracer_of_the_program() is None
        for name in READERS:
            assert harness.load_reader(name, cell.root).read(context) is None
    finally:
        set_default_tracer(previous)


def test_queue_wait_reads_each_steps_first_admission(tiny_root, tracer):
    """Two requests admitted in one step and one in the next: the second of the
    first step also waited out the insert before it, and is left out."""
    t0 = time.perf_counter()
    for step, waits in enumerate([{1: 0.001, 2: 0.017}, {3: 0.002}]):
        requests = {rid: tracer.start_span("serve.request", request_id=rid) for rid in waits}
        with tracer.span("serve.step", step=step):
            for rid, wait in waits.items():
                requests[rid].event("admitted", queue_wait_s=wait)
                with tracer.span("serve.insert", request_id=rid):
                    pass
        for span in requests.values():
            span.end()
    context = {"window": {"t0": t0, "t1": time.perf_counter()}, "trace_span": (None, None)}
    assert harness.load_reader("queue_wait_ms", tiny_root).read(context) == pytest.approx(1.5)
