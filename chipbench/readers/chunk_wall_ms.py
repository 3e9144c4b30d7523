"""Median wall time of one `router.step()` in the window, on the client loop's
clock: admissions, one decode chunk and the drain of its tokens."""

from chipbench import harness


def read(context):
    window = context["window"]
    walls = [(b - a) * 1e3 for a, b, *_ in window["steps"] if a >= window["t0"]]
    return harness.median(walls) if walls else None
