"""Share of the device's busy time, in the traced window, that the insert
(prefill) modules took."""

from chipbench import trace_reduce


def read(context):
    cell, reduced = context["cell"], context["trace"]
    seconds, runs = trace_reduce.module_seconds(reduced, cell.spec["modules"]["insert"])
    if not runs or not reduced["busy_s"]:
        return None
    return seconds / reduced["busy_s"] * 100.0
