"""The part of a request's first-token time that the device (and the one
readback) had: median `on_device_s` of the `handed_back` event — from its
insert's dispatch call returning to its first token on the host: the insert,
the chunk the token rides, the readback — over the requests submitted AND
handed back inside one clean stretch of the window: before the capture's start
is asked for, or inside the capture (`captured_spans.clean_stretches`). A
request that waits across the profiler's start or stop is no reading. A few
requests a run at the open cell's rate: a small sample."""

from chipbench import captured_spans, harness


def read(context):
    on_device = []
    for stretch in captured_spans.clean_stretches(context):
        placed = captured_spans.place(stretch)
        if placed is None:
            return None
        for record in captured_spans.spans("serve.request", placed):
            on_device += [e["attrs"]["on_device_s"] for e in record.get("events", ())
                          if e["name"] == "handed_back" and e["t_unix"] < placed[1]
                          and "on_device_s" in e["attrs"]]
    return harness.median(on_device) * 1e3 if on_device else None
