"""Median time the host waits in `next(loader)` for the next prepared batch.
The benchmark's own clock."""

from chipbench import harness


def read(context):
    waits = context["window"]["data_wait"]
    return harness.median(waits) * 1e3 if waits else None
