"""The program's own spans, for the readers whose `source` is `program_span`.

The engine and the trainer record their spans in the process-wide tracer
(`accelerate_tpu.telemetry.default_tracer()`: a `Router` and its engine use it
when handed none, and its ring outlives `router.close()`). A reader takes the
spans that START in the part of the measured window that follows the capture:
the profiler's start and stop stall the client loop for seconds in a traced
run, and a step stretched by them is no reading of the program. The window's
instants are the client loop's (`time.perf_counter`); the records carry the
tracer's own timeline, and `Tracer.now()` read beside `time.perf_counter()`
gives the shift between the two.

A program without these spans (a parent commit that lacks `Tracer.now` or the
`serve.step` tree) gives None: the metric is then left off the line. A ring
that has wrapped past the first instant asked for raises: a wrapped ring must
never read as a short window.
"""

from __future__ import annotations

import time


def tracer_of_the_program():
    """The process-wide tracer, or None where the program has none a reader
    can map onto the client's clock."""
    try:
        from accelerate_tpu.telemetry import default_tracer
    except ImportError:
        return None
    tracer = default_tracer()
    return tracer if callable(getattr(tracer, "now", None)) else None


def bounds(context: dict) -> tuple:
    """`(after, before)` on the client loop's clock: from the capture's stop
    (the window's start where nothing was captured) to the window's close."""
    window = context["window"]
    stopped = context["trace_span"][1]
    return (window["t0"] if stopped is None else max(window["t0"], stopped)), window["t1"]


def spans(context: dict, name: str) -> list | None:
    """The recorded spans called `name` that start inside `bounds(context)`,
    oldest first; None where the program records no spans a reader can place."""
    tracer = tracer_of_the_program()
    if tracer is None:
        return None
    shift = tracer.now() - time.perf_counter()
    after, before = (t + shift for t in bounds(context))
    records = tracer.recorder.records()
    if records and len(records) >= tracer.recorder.capacity:
        oldest = records[0]
        arrived = oldest.get("end_unix", oldest.get("t_unix"))
        if arrived >= after:
            raise RuntimeError(
                f"the flight recorder's ring ({tracer.recorder.capacity} records) has wrapped past "
                f"the window's first instant: its oldest record arrived {arrived - after:.3f} s "
                "into it, so the spans before it are lost")
    return [r for r in records
            if r.get("kind") == "span" and r["name"] == name and after <= r["start_unix"] < before]


def request_events(context: dict, event: str, attr: str) -> list | None:
    """`attr` of the `event` of every `serve.request` span submitted inside
    `bounds(context)` that has one."""
    requests = spans(context, "serve.request")
    if requests is None:
        return None
    return [e["attrs"][attr] for r in requests for e in r.get("events", ())
            if e["name"] == event and e["attrs"].get(attr) is not None]
