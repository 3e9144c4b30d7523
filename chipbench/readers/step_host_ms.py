"""The engine's own time in one `step()`: median `host_s` of the `serve.step`
spans that start in the window, after the capture — the step's duration less
the seconds its readbacks waited for the device (`device_wait_s`: the inserts'
first tokens and the chunk's outputs). One reader for `step_host_ms.serve` and
`step_host_ms.open`; what the client loop spends between two steps is not in it
(`chunk_wall_ms` less the step's duration bounds that from outside)."""

from chipbench import harness, program_spans


def read(context):
    steps = program_spans.spans(context, "serve.step")
    host = [r["attrs"]["host_s"] for r in steps or () if "host_s" in r["attrs"]]
    return harness.median(host) * 1e3 if host else None
