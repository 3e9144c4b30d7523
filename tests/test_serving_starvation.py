"""The engine's own account of a starved device (`serve.step.starved_*`,
`gap_s` / `gap_cause`, `serving_device_starved_seconds_total`,
`stats["device_starved"]`), a chunk's cadence (`serve.decode_chunk.cadence_s`,
`serving_chunk_seconds`) and a request's first-token time by phase (the
`handed_back` event). A tiny engine on the CPU: every assertion is on what the
account charges to whom and on what adds up; no number here is a speed."""

import statistics
import time

import numpy as np
import pytest

from accelerate_tpu.serving import STARVED_CAUSES, ContinuousBatcher, Request
from accelerate_tpu.telemetry import FlightRecorder, Tracer

from test_serving import _model, _serve  # all at once, or each request when a slot is free for it

IN_STEP = ("admit", "push", "dispatch", "drain")
LENGTHS = (9, 1, 3, 6, 12, 2, 7)
ROOM = 2e-5  # attributes are rounded to the microsecond, a few of them summed


def _request(rid, max_new, rng):
    return Request(rid, rng.integers(1, 128, (6,)).astype(np.int32), max_new_tokens=max_new)


class Run:
    """One engine stepped to the end, with what it recorded."""

    def __init__(self, backlog):
        self.recorder = FlightRecorder()
        self.engine = ContinuousBatcher(_model(), num_slots=2, max_length=64, chunk_size=4,
                                        tracer=Tracer(recorder=self.recorder, category="serve"))
        rng = np.random.default_rng(36)
        _serve(self.engine, [_request(i, n, rng) for i, n in enumerate(LENGTHS)], backlog)
        self.stats = self.engine.stats
        self.chunk_seconds = self.engine.metrics.get("serving_chunk_seconds")
        records = self.recorder.records()
        self.steps = [r for r in records if r["name"] == "serve.step"]
        self.chunks = [r for r in records if r["name"] == "serve.decode_chunk"]
        self.inserts = [r for r in records if r["name"] == "serve.insert"]
        self.handed = {r["attrs"]["request_id"]: e["attrs"] for r in records if r["name"] == "serve.request"
                       for e in r["events"] if e["name"] == "handed_back"}
        self.engine.close()


@pytest.fixture(scope="module")
def runs():
    return {"sync": Run(backlog=False), "backlog": Run(backlog=True)}


@pytest.mark.parametrize("mode", ["sync", "backlog"])
def test_the_parts_sum_to_the_steps_starved_time_which_is_host_time(runs, mode):
    for step in runs[mode].steps:
        attrs = step["attrs"]
        assert sum(attrs[f"starved_{cause}_s"] for cause in IN_STEP) == pytest.approx(attrs["starved_s"], abs=ROOM)
        assert 0.0 <= attrs["starved_s"] <= attrs["host_s"] + ROOM
        assert attrs["gap_s"] >= 0.0 and attrs["gap_cause"] in ("client", "no_work", "covered")


@pytest.mark.parametrize("cause", IN_STEP)
def test_a_synchronous_step_charges_the_part_that_left_the_device_empty(runs, cause):
    """With nothing in flight when a step begins, the device sits empty until
    the step's first dispatch returns — an insert's (`admit`), else the
    chunk's (`push`, `dispatch`) — and again from its readback to its return
    (`drain`)."""
    steps = [s["attrs"] for s in runs["sync"].steps]
    assert all(s["in_flight_at_return"] == 0 and s["gap_cause"] != "covered" for s in steps)
    admitting = [s for s in steps if s["inserts"]]
    launching = [s for s in steps if not s["inserts"] and s["dispatched_ahead"]]
    assert admitting and launching
    if cause == "admit":
        # an insert went out first: the push and the launch run beside it
        assert all(s["starved_admit_s"] > 0 and s["starved_push_s"] == s["starved_dispatch_s"] == 0.0
                   for s in admitting)
        assert all(s["starved_admit_s"] <= s["admit_s"] + ROOM for s in steps)
    elif cause == "drain":
        assert all(s["starved_drain_s"] + ROOM >= s["drain_s"] > 0 for s in steps if s["waits"])
    else:
        assert all(s[f"starved_{cause}_s"] + ROOM >= s[f"{cause}_s"] > 0 for s in launching)


def test_with_a_backlog_a_step_under_a_chunk_in_flight_charges_nothing(runs):
    steps = [s["attrs"] for s in runs["backlog"].steps]
    assert max(s["in_flight_at_return"] for s in steps) == 1
    ran_ahead = 0
    for before, step in zip(steps, steps[1:]):
        if before["in_flight_at_return"]:
            # the gap under a chunk in flight is nobody's, and nothing is charged for it
            assert step["gap_cause"] == "covered"
            if step["in_flight_at_return"]:
                ran_ahead += 1
                assert step["starved_s"] == 0.0
        else:
            assert step["gap_cause"] != "covered"
    assert ran_ahead >= 2
    assert steps[0]["starved_admit_s"] > 0  # the first step found the device empty


def test_the_step_that_ends_a_backlog_charges_its_drain(runs):
    """It reads the last chunk in flight back and dispatches none: the device
    is empty from that readback on."""
    steps = [s["attrs"] for s in runs["backlog"].steps]
    ending = [step for before, step in zip(steps, steps[1:])
              if before["in_flight_at_return"] and not step["in_flight_at_return"]]
    assert ending
    for step in ending:
        assert step["starved_drain_s"] > 0
        assert step["starved_admit_s"] == step["starved_push_s"] == step["starved_dispatch_s"] == 0.0


@pytest.fixture(scope="module")
def gaps():
    """One engine left unstepped three ways: with nothing pending, with an
    active slot and a queued request, and — one step on — under a chunk in
    flight. Returns the step after each, and what the counters charged."""
    recorder = FlightRecorder()
    engine = ContinuousBatcher(_model(), num_slots=1, max_length=64, chunk_size=4,
                               tracer=Tracer(recorder=recorder, category="serve"))
    rng = np.random.default_rng(7)

    def charged():
        return dict(engine.stats["device_starved"])

    engine.submit(_request(0, 2, rng))
    while engine.pending:
        engine.step()
    marks = {"start": charged()}
    time.sleep(0.05)  # nothing pending: nobody's
    engine.submit(_request(1, 30, rng))
    engine.step()
    marks["no_work"] = charged()
    assert engine.pending and not engine.stats["chunks_ahead_share"]
    engine.submit(_request(2, 3, rng))  # queued behind the one slot
    time.sleep(0.05)  # work pending, nothing in flight, nobody steps: the client's
    engine.step()
    marks["client"] = charged()
    time.sleep(0.05)  # that step ran ahead: its chunk covers the gap
    engine.step()
    marks["covered"] = charged()
    steps = [r["attrs"] for r in recorder.records() if r["name"] == "serve.step"]
    engine.close()
    return steps[-3:], marks


@pytest.mark.parametrize("index,cause", [(0, "no_work"), (1, "client"), (2, "covered")])
def test_the_gap_between_two_steps_is_charged_to_who_had_it(gaps, index, cause):
    steps, marks = gaps
    step = steps[index]
    assert step["gap_cause"] == cause and step["gap_s"] >= 0.05
    before = marks[("start", "no_work", "client")[index]]
    after = marks[cause]
    for name in ("client", "no_work"):
        assert after[name] - before[name] == pytest.approx(step["gap_s"] if name == cause else 0.0, abs=1e-4)
    if cause == "covered":
        assert steps[1]["in_flight_at_return"] == 1 and step["starved_s"] == 0.0


@pytest.mark.parametrize("cause", STARVED_CAUSES)
@pytest.mark.parametrize("mode", ["sync", "backlog"])
def test_the_counters_totals_are_the_spans_sums(runs, mode, cause):
    run = runs[mode]
    steps = [s["attrs"] for s in run.steps]
    if cause in IN_STEP:
        recorded = sum(s[f"starved_{cause}_s"] for s in steps)
    else:
        recorded = sum(s["gap_s"] for s in steps if s["gap_cause"] == cause)
    assert run.stats["device_starved"][cause] == pytest.approx(recorded, abs=len(steps) * 1e-6)
    assert set(run.stats["device_starved"]) == set(STARVED_CAUSES) | {"share"}


@pytest.mark.parametrize("mode", ["sync", "backlog"])
def test_the_share_is_the_hosts_part_of_the_wall_since_the_first_step(runs, mode):
    run = runs[mode]
    starved = run.stats["device_starved"]
    wall = run.steps[-1]["end_unix"] - run.steps[0]["start_unix"]
    host_caused = sum(starved[cause] for cause in STARVED_CAUSES if cause != "no_work")
    assert 0.0 < starved["share"] <= host_caused / wall + 1e-6  # read a moment after the last step
    assert host_caused <= wall
    fresh = ContinuousBatcher(_model(), num_slots=1, max_length=64, chunk_size=4)
    assert fresh.stats["device_starved"]["share"] is None  # never stepped
    fresh.close()


@pytest.mark.parametrize("mode", ["sync", "backlog"])
def test_a_chunks_cadence_is_one_chunk_whether_or_not_it_ran_ahead(runs, mode):
    """`cadence_s` is at most the span's extent and the cadences of a run add
    up to no more than its wall, where the spans of chunks dispatched ahead
    overlap their predecessors; `serving_chunk_seconds` observes the cadence."""
    run = runs[mode]
    chunks = run.chunks
    assert len(chunks) == run.stats["chunks"] == run.chunk_seconds.count
    assert all(0.0 < c["attrs"]["cadence_s"] <= c["duration_s"] + ROOM for c in chunks)
    cadences = sum(c["attrs"]["cadence_s"] for c in chunks)
    assert run.chunk_seconds.sum == pytest.approx(cadences, abs=len(chunks) * 1e-6)
    # the histogram's median is one chunk's cadence, to the width of a bucket (4 a decade)
    cadence = [c["attrs"]["cadence_s"] for c in chunks]
    assert statistics.median_low(cadence) / 1.8 <= run.chunk_seconds.quantile(0.5) <= statistics.median_high(cadence) * 1.8
    wall = run.steps[-1]["end_unix"] - run.steps[0]["start_unix"]
    longest_step = max(s["duration_s"] for s in run.steps)
    assert cadences <= wall + ROOM
    ahead = [c for c in chunks if c["attrs"]["ahead"]]
    assert bool(ahead) is (mode == "backlog")
    if ahead:
        # since the readback before it, not since its own dispatch under the chunk before it
        assert all(c["attrs"]["cadence_s"] < c["duration_s"] for c in ahead)
        read_back = sorted(c["end_unix"] for c in chunks)
        assert cadences >= (read_back[-1] - read_back[0]) - longest_step
        assert sum(c["duration_s"] for c in ahead) > sum(c["attrs"]["cadence_s"] for c in ahead)


@pytest.mark.parametrize("mode", ["sync", "backlog"])
def test_a_requests_four_phases_add_up_to_its_first_token_time(runs, mode):
    run = runs[mode]
    assert sorted(run.handed) == list(range(len(LENGTHS)))
    for rid, handed in run.handed.items():
        phases = [handed[k] for k in ("queue_wait_s", "admit_host_s", "on_device_s", "held_s")]
        assert all(p >= 0.0 for p in phases), handed
        assert sum(phases) == pytest.approx(handed["ttft_s"], abs=1e-4)
        result = run.engine.results[rid]
        assert result.submit_time <= result.insert_dispatched_time <= result.first_token_time
        assert handed["on_device_s"] == pytest.approx(
            result.first_token_time - result.insert_dispatched_time, abs=ROOM)
    assert all("device_wait_s" not in r["attrs"] for r in run.inserts)  # dispatch only: nothing to wait for


@pytest.mark.parametrize("mode", ["sync", "backlog"])
def test_inserts_ahead_counts_a_steps_earlier_admissions(runs, mode):
    run = runs[mode]
    by_step = {}
    for insert in sorted(run.inserts, key=lambda r: r["start_unix"]):
        by_step.setdefault(insert["parent_id"], []).append(insert["attrs"]["request_id"])
    assert max(len(rids) for rids in by_step.values()) >= 2
    for rids in by_step.values():
        assert [run.handed[rid]["inserts_ahead"] for rid in rids] == list(range(len(rids)))
        # a later admission also waits out the host part of every insert before its own
        waits = [run.handed[rid]["queue_wait_s"] for rid in rids]
        assert waits == sorted(waits)


def test_a_first_token_rides_the_chunk_its_step_dispatches_or_the_buffer():
    recorder = FlightRecorder()
    engine = ContinuousBatcher(_model(), num_slots=2, max_length=64, chunk_size=4,
                               tracer=Tracer(recorder=recorder, category="serve"))
    rng = np.random.default_rng(3)
    engine.run([_request(0, 1, rng)])  # alone: no slot decodes, the step reads the buffer itself
    engine.run([_request(1, 5, rng)])
    handed = {r["attrs"]["request_id"]: e["attrs"] for r in recorder.records() if r["name"] == "serve.request"
              for e in r["events"] if e["name"] == "handed_back"}
    engine.close()
    assert handed[0]["rode_chunk"] is False and handed[1]["rode_chunk"] is True
    assert handed[0]["inserts_ahead"] == handed[1]["inserts_ahead"] == 0


def test_the_router_carries_the_engines_account():
    from accelerate_tpu.router import Router

    router = Router(_model(), replicas=1, num_slots=2, max_length=64, chunk_size=4)
    rng = np.random.default_rng(5)
    router.submit(_request(0, 6, rng))
    while router.pending:
        router.step()
    starved = router.stats["per_replica"][0]["device_starved"]
    router.close()
    assert set(starved) == set(STARVED_CAUSES) | {"share"}
    assert starved["admit"] > 0 and starved["drain"] > 0 and 0.0 < starved["share"] <= 1.0
