"""Wall time of one admission's insert, dispatch through the readback of the
first token: median duration of the `serve.insert` spans that start in the
window, after the capture. The engine's clock around what `prefill_device_pct`
sees from the device."""

from chipbench import harness, program_spans


def read(context):
    inserts = program_spans.spans(context, "serve.insert")
    return harness.median([r["duration_s"] for r in inserts]) * 1e3 if inserts else None
