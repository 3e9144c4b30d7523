"""What a slot holds in by-slot leaves — `state_bytes_per_slot`, which the
engine reads off the live cache's shapes and dtypes — over what the
configuration publishes for a request whatever its length: every layer's `H`
in float32 and the convolution's last inputs in the serving type
(`shapes_ssm_hybrid.state_bytes_per_slot`). 100 where nothing is padded or
widened; the guard against a stored layout that holds or moves more than the
model needs. A program without the counter gives None."""

from chipbench import chunk_counters, shapes_ssm_hybrid


def read(context):
    held = chunk_counters.mean(context, "state_bytes_per_slot")
    if held is None:
        return None
    cell = context["cell"]
    return held / shapes_ssm_hybrid.state_bytes_per_slot(cell.config, cell.spec["dtype"]) * 100.0
