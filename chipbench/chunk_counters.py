"""The counters the engine hands out with every decode chunk, for the readers
whose `source` is `program_counter` and for the roofline shares that need them
(`kv_row_values`, `expert_tokens_max` / `_mean`, `experts_touched`).

They ride the recorded `serve.decode_chunk` spans of the process-wide tracer,
whose ring outlives `router.close()` (`program_spans.py`). Unlike a span's
duration a count is not stretched by the profiler's start and stop, so these
readers take every chunk that STARTS in the measured window, the captured part
included. A program whose chunks carry no such counter (a parent commit) gives
None and the metric is left off the line; a ring that has wrapped past the
window's first instant raises, as there.
"""

from __future__ import annotations

from chipbench import program_spans


def chunks(context: dict, needs: tuple) -> list | None:
    """Attributes of the window's `serve.decode_chunk` spans, oldest first, or
    None where the program records none that carry every key of `needs`."""
    window = context["window"]
    whole = dict(context, trace_span=(None, None))  # program_spans.bounds: from the window's start
    if window.get("t0") is None:
        return None
    spans = program_spans.spans(whole, "serve.decode_chunk")
    if not spans:
        return None
    attrs = [r["attrs"] for r in spans]
    return attrs if all(key in a for a in attrs for key in needs) else None


def mean(context: dict, key: str) -> float | None:
    """The window's mean of one counter over its decode chunks, or None."""
    counted = chunks(context, (key,))
    return sum(a[key] for a in counted) / len(counted) if counted else None
