"""From `submit()` to admission (a slot and its pages found, the insert about
to be dispatched), for the FIRST admission of each step: median `queue_wait_s`
of the `admitted` event of those `serve.request` spans, over the steps whose
first `serve.insert` starts in the window, after the capture.

All admissions together are bimodal: a later admission of the same step also
waits out every insert before it (`insert_wall_ms` each), so their median
flips between the modes with the share of requests that shared a step, and
their mean is taken over by the few requests of an episode of full slots. The
first admission's wait has neither in it: it is the engine's own queue and the
plan of pages. A client that submits only between two steps holds the request
itself while the step in flight runs (the generator's lateness, not this), so
below the knee it reads about a millisecond, and seconds once a request has to
wait for a slot in more than half of the steps that admit."""

from chipbench import harness, program_spans


def read(context):
    inserts = program_spans.spans(context, "serve.insert")
    requests = program_spans.spans(context, "serve.request")
    if not inserts or not requests:
        return None
    waited = {r["attrs"]["request_id"]: e["attrs"]["queue_wait_s"]
              for r in requests for e in r.get("events", ()) if e["name"] == "admitted"}
    first_of_step = {}
    for insert in sorted(inserts, key=lambda r: r["start_unix"], reverse=True):
        first_of_step[insert["parent_id"]] = insert["attrs"]["request_id"]
    waits = [waited[rid] for rid in first_of_step.values() if rid in waited]
    return harness.median(waits) * 1e3 if waits else None
