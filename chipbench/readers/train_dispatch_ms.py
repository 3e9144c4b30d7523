"""Median time the host spends in one `step_fn(batch)` call until it returns:
the enqueue of the fused step, no fence. The benchmark's own clock."""

from chipbench import harness


def read(context):
    calls = context["window"]["dispatch"]
    return harness.median(calls) * 1e3 if calls else None
