"""The program's own spans over the CAPTURED stretch of a traced window, for
the readers that must read on whatever machine the run lands.

`program_spans.py` takes the spans that start after the capture's STOP, and
`jax.profiler.stop_trace()` may return after the window has closed (36 us an
event of the capture): its readers then find nothing. Nothing here waits for
the stop. The profiler's start has returned by `trace_span[0]`, and its stop is
called from `TraceWindow.poll` between two steps, once the capture's length has
passed: so no span that STARTS in `[trace_span[0], trace_span[0] + length_s)`
is stalled by either, and those are also the only seconds the device metrics
of the run describe. Where nothing was captured (`trace_span` `(None, None)`)
the stretch is the whole window.

Instants are the client loop's (`time.perf_counter`) and are mapped onto the
records' timeline as `program_spans.spans` maps them: `Tracer.now()` read
beside `time.perf_counter()`. A program without such a tracer gives None; a
ring that has wrapped past a stretch's first instant raises.
"""

from __future__ import annotations

import time

from chipbench import program_spans


def captured(context: dict) -> tuple:
    """`(after, before)` on the client loop's clock: the capture as it was
    asked for, from where the profiler's start returned."""
    window = context["window"]
    started = context["trace_span"][0]
    if started is None:
        return window["t0"], window["t1"]
    return started, started + context["cell"].spec.get("trace", {}).get("length_s", 3.0)


def clean_stretches(context: dict) -> list:
    """The parts of the window that neither the profiler's start nor its stop
    stalls: before the capture is asked for, and the capture itself."""
    started = context["trace_span"][0]
    if started is None:
        return [captured(context)]
    t0 = context["window"]["t0"]
    asked = t0 + context["cell"].spec.get("trace", {}).get("start_after_s", 2.0)
    return [(t0, min(asked, started)), captured(context)]


def place(stretch: tuple) -> tuple | None:
    """A stretch of the client's clock on the records' timeline (`start_unix`,
    an event's `t_unix`), or None where the program has no tracer a reader can
    map onto the client's clock."""
    tracer = program_spans.tracer_of_the_program()
    if tracer is None:
        return None
    shift = tracer.now() - time.perf_counter()
    return stretch[0] + shift, stretch[1] + shift


def spans(name: str, placed: tuple) -> list:
    """The recorded spans called `name` that start inside `placed` (a stretch
    on the records' timeline: `place`), oldest first."""
    after, before = placed
    recorder = program_spans.tracer_of_the_program().recorder
    records = recorder.records()
    if records and len(records) >= recorder.capacity:
        oldest = records[0]
        arrived = oldest.get("end_unix", oldest.get("t_unix"))
        if arrived >= after:
            raise RuntimeError(
                f"the flight recorder's ring ({recorder.capacity} records) has wrapped past "
                f"the stretch's first instant: its oldest record arrived {arrived - after:.3f} s "
                "into it, so the spans before it are lost")
    return [r for r in records
            if r.get("kind") == "span" and r["name"] == name and after <= r["start_unix"] < before]
