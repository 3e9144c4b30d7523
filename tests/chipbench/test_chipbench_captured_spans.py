"""The readers that take the engine's own account from the CAPTURED stretch of
a traced window (`chipbench/captured_spans.py`), on the tiny cells of
conftest.py: each reads a number whether or not the capture's stop ever came
back, nothing is read from a stretch the profiler stalls, and a ring that has
wrapped is an error and not a short window. No number here is a speed."""

import time
import types

import pytest

from accelerate_tpu.telemetry import FlightRecorder, Tracer, set_default_tracer
from chipbench import captured_spans, harness

READERS = ("host_exposed_pct.serve", "host_exposed_pct.open", "ttft_on_device_ms")
CELLS = ("neox-tiny.tiny-backlog", "neox-tiny.tiny-open")


@pytest.fixture(scope="module")
def served(tiny_root, ledger):
    """One short window a tiny cell, each recorded by a tracer of its own."""
    out = {}
    for workload in CELLS:
        tracer = Tracer(recorder=FlightRecorder())
        previous = set_default_tracer(tracer)
        try:
            cell = harness.Cell(workload, tiny_root)
            driver = harness.load_module("drivers", cell.spec["driver"], tiny_root)
            run = driver.serve_once(cell, 2**31 + 36, 1.5, harness.TraceWindow(False, 0.0, 0.0), ledger,
                                    time.perf_counter())
        finally:
            set_default_tracer(previous)
        assert run["correct"]
        out[workload] = (cell, run["context"], tracer)
    return out


@pytest.fixture()
def of(served):
    """`of(workload, trace_span)`: the cell and its run's context as a traced
    run would leave it, with the run's tracer the process-wide one."""
    swapped = []

    def bind(workload, trace_span):
        cell, context, tracer = served[workload]
        swapped.append(set_default_tracer(tracer))
        return cell, dict(context, trace_span=trace_span(context["window"]))

    yield bind
    for previous in reversed(swapped):
        set_default_tracer(previous)


SPANS = {
    "stop-never-came-back": lambda window: (window["t0"] + 0.2, window["t0"] + 10**6),
    "nothing-captured": lambda window: (None, None),
}


# A backlog keeps the queue full: a request submitted inside the short window
# may still be queued when it closes, so only the open cell must read a
# request's phases (the benchmark asks `ttft_on_device_ms` of its open cell alone).
@pytest.mark.parametrize("span", list(SPANS))
@pytest.mark.parametrize("workload,reader", [
    (CELLS[0], "host_exposed_pct.serve"), (CELLS[1], "host_exposed_pct.open"), (CELLS[1], "ttft_on_device_ms")])
def test_a_reader_reads_a_number_whatever_became_of_the_captures_stop(of, workload, reader, span):
    cell, context = of(workload, SPANS[span])
    value = harness.load_reader(reader, cell.root).read(context)
    assert isinstance(value, float) and value >= 0.0
    if reader.startswith("host_exposed_pct"):
        assert value <= 100.0


@pytest.mark.parametrize("span", list(SPANS))
def test_the_captured_stretch_depends_on_the_captures_start_alone(of, span):
    cell, context = of(CELLS[0], SPANS[span])
    window = context["window"]
    after, before = captured_spans.captured(context)
    if span == "nothing-captured":
        assert (after, before) == (window["t0"], window["t1"])
        assert captured_spans.clean_stretches(context) == [(after, before)]
    else:  # the tiny cells name no capture: the driver's defaults, 3 s from 2 s in
        assert (after, before) == (window["t0"] + 0.2, window["t0"] + 3.2)
        assert captured_spans.clean_stretches(context) == [(window["t0"], window["t0"] + 0.2), (after, before)]
    placed = captured_spans.place((after, before))
    steps = captured_spans.spans("serve.step", placed)
    assert steps and all(placed[0] <= s["start_unix"] < placed[1] for s in steps)
    assert len(steps) <= len(window["steps"])


def test_a_steady_backlog_exposes_next_to_nothing(of):
    """Under a chunk in flight a step charges nothing and its gap is covered:
    what is left is the one step that starts running ahead."""
    cell, context = of(CELLS[0], SPANS["stop-never-came-back"])
    steps = captured_spans.spans("serve.step", captured_spans.place(captured_spans.captured(context)))
    steady = [s["attrs"] for s in steps if s["attrs"]["gap_cause"] == "covered" and s["attrs"]["in_flight_at_return"]]
    assert len(steady) >= len(steps) - 2 and all(s["starved_s"] == 0.0 for s in steady)
    assert harness.load_reader("host_exposed_pct.serve", cell.root).read(context) < 5.0


def test_the_open_cells_median_request_adds_up(of):
    cell, context = of(CELLS[1], SPANS["nothing-captured"])
    requests = captured_spans.spans("serve.request", captured_spans.place(captured_spans.captured(context)))
    handed = [e["attrs"] for r in requests for e in r.get("events", ()) if e["name"] == "handed_back"]
    assert len(handed) >= 5
    for attrs in handed:
        phases = sum(attrs[k] for k in ("queue_wait_s", "admit_host_s", "on_device_s", "held_s"))
        assert phases == pytest.approx(attrs["ttft_s"], abs=1e-4)
    value = harness.load_reader("ttft_on_device_ms", cell.root).read(context)
    assert min(a["on_device_s"] for a in handed) * 1e3 <= value <= max(a["on_device_s"] for a in handed) * 1e3


@pytest.fixture()
def tracer():
    mine = Tracer(recorder=FlightRecorder())
    previous = set_default_tracer(mine)
    yield mine
    set_default_tracer(previous)


def _cell(start_after_s=2.0, length_s=3.0):
    return types.SimpleNamespace(spec={"trace": {"start_after_s": start_after_s, "length_s": length_s}})


def _step(tracer, gap_s, gap_cause, starved_s, busy_s=0.002):
    with tracer.span("serve.step") as span:
        time.sleep(busy_s)
        span.annotate(starved_s=starved_s, gap_s=gap_s, gap_cause=gap_cause)


@pytest.mark.parametrize("gap_cause,exposed", [("client", True), ("no_work", False), ("covered", False)])
def test_a_gap_counts_as_exposure_only_where_it_was_the_clients(tiny_root, tracer, gap_cause, exposed):
    """Three steps of 1 ms starved each; the middle one after a 10 ms gap. The
    first step's gap reaches back before the stretch — the profiler's start
    lies there — and counts only from the stretch's start."""
    started = time.perf_counter()
    time.sleep(0.003)
    _step(tracer, gap_s=5.0, gap_cause="client", starved_s=0.001)
    first_start = tracer.recorder.records()[-1]["start_unix"] - (tracer.now() - time.perf_counter())
    time.sleep(0.010)
    _step(tracer, gap_s=0.010, gap_cause=gap_cause, starved_s=0.001)
    _step(tracer, gap_s=0.0, gap_cause="covered", starved_s=0.001)
    context = {"cell": _cell(), "window": {"t0": started - 2.5, "t1": started + 60.0},
               "trace_span": (started, started + 10**6)}
    steps = [r for r in tracer.recorder.records() if r["name"] == "serve.step"]
    clipped = first_start - started
    assert 0.003 <= clipped < 1.0
    wall = sum(r["duration_s"] for r in steps) + clipped + 0.010
    starved = 0.003 + clipped + (0.010 if exposed else 0.0)
    value = harness.load_reader("host_exposed_pct.open", tiny_root).read(context)
    assert value == pytest.approx(100.0 * starved / wall, rel=1e-3)


def test_a_request_counts_only_inside_one_clean_stretch(tiny_root, tracer):
    """Submitted and handed back before the capture is asked for, or inside
    the capture: read. Submitted before the profiler's start and handed back
    after it, or handed back after the capture's length: not."""
    now = time.perf_counter()
    context = {"cell": _cell(start_after_s=0.2, length_s=0.4), "window": {"t0": now, "t1": now + 50.0},
               "trace_span": (now + 0.5, now + 10**6)}
    plan = [  # (submit at, handed back at, on_device_s) from the window's start
        (0.00, 0.05, 0.011),  # clean: before the capture is asked for
        (0.10, 0.60, 0.500),  # waited across the profiler's start
        (0.55, 0.65, 0.013),  # clean: inside the capture
        (0.70, 1.10, 0.700),  # handed back after the capture's length
    ]
    spans = {}
    for at in sorted({t for submit, back, _ in plan for t in (submit, back)}):
        time.sleep(max(0.0, now + at + 0.002 - time.perf_counter()))
        for i, (submit, back, on_device_s) in enumerate(plan):
            if submit == at:
                spans[i] = tracer.start_span("serve.request", request_id=i)
            if back == at:
                spans[i].event("handed_back", on_device_s=on_device_s, ttft_s=back - submit)
                spans[i].end()
    assert harness.load_reader("ttft_on_device_ms", tiny_root).read(context) == pytest.approx(12.0)


def test_a_wrapped_ring_raises_and_a_program_without_the_account_reads_nothing(tiny_root, ledger):
    small = Tracer(recorder=FlightRecorder(capacity=16))
    previous = set_default_tracer(small)
    try:
        cell = harness.Cell(CELLS[0], tiny_root)
        driver = harness.load_module("drivers", cell.spec["driver"], tiny_root)
        out = driver.serve_once(cell, 7, 1.5, harness.TraceWindow(False, 0.0, 0.0), ledger, time.perf_counter())
        window = out["context"]["window"]
        context = dict(out["context"], trace_span=(window["t0"] + 0.2, window["t0"] + 10**6))
        assert len(small.recorder.records()) == 16
        for reader in READERS:
            with pytest.raises(RuntimeError, match="wrapped"):
                harness.load_reader(reader, cell.root).read(context)

        # a stretch the ring still covers from before its start reads, full or not
        late = dict(context, trace_span=(time.perf_counter(), None))
        assert captured_spans.spans("serve.step", captured_spans.place(captured_spans.captured(late))) == []
        assert harness.load_reader("host_exposed_pct.serve", cell.root).read(late) is None
    finally:
        set_default_tracer(previous)

    # the parent commit's steps and requests carry no such attributes: nothing to read, and no error
    older = Tracer(recorder=FlightRecorder())
    previous = set_default_tracer(older)
    try:
        t0 = time.perf_counter()
        request = older.start_span("serve.request", request_id=0)
        with older.span("serve.step") as span:
            span.annotate(host_s=0.001, device_wait_s=0.002)
        request.event("handed_back", held_s=0.0, ttft_s=0.003)
        request.end()
        context = {"cell": _cell(), "window": {"t0": t0, "t1": time.perf_counter()}, "trace_span": (None, None)}
        for reader in READERS:
            assert harness.load_reader(reader, tiny_root).read(context) is None
    finally:
        set_default_tracer(previous)

    # nor does a program whose tracer cannot be mapped onto the client's clock
    class Older:
        recorder = small.recorder

    previous = set_default_tracer(Older())
    try:
        for reader in READERS:
            assert harness.load_reader(reader, tiny_root).read(context) is None
    finally:
        set_default_tracer(previous)
