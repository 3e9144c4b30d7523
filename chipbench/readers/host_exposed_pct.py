"""Share of the captured stretch's wall time in which the engine KNEW the device
had nothing enqueued and the host was the cause: over the `serve.step` spans
that start in the capture, 100 x the sum of `starved_s` (in-step: admission,
push, launch and drain with nothing in flight) and of the gaps before a step
that were the client's (`gap_cause` "client": work was pending and nobody
stepped), over the sum of the steps' durations and of every gap — a gap counted
only as far back as the stretch's start, so that the profiler's own start is in
nobody's gap. Gaps with nothing pending (`no_work`) are the offered load's and
gaps under a chunk in flight (`covered`) starve nothing: both are wall, neither
is exposure.

A LOWER BOUND of `device_idle_pct` over the same seconds: the engine cannot see
the device idling inside its one wait (the readback's latency, a launch's
tail). One reader for `host_exposed_pct.serve` and `host_exposed_pct.open`."""

from chipbench import captured_spans


def read(context):
    placed = captured_spans.place(captured_spans.captured(context))
    if placed is None:
        return None
    exposed = wall = 0.0
    for record in captured_spans.spans("serve.step", placed):
        attrs = record["attrs"]
        if "starved_s" not in attrs:
            return None  # a program that keeps no such account
        gap_s = min(attrs["gap_s"], record["start_unix"] - placed[0])
        exposed += attrs["starved_s"] + (gap_s if attrs["gap_cause"] == "client" else 0.0)
        wall += record["duration_s"] + gap_s
    return 100.0 * exposed / wall if wall > 0 else None
