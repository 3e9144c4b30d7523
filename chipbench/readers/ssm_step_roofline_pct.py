"""The one-token update of the state-space recurrence against its memory
roofline: the program's Pallas kernel is the operation `ssm_step`, one call a
layer a decode step (an insert runs the chunked form, not this kernel). A call
has to read and to write the recurrent state `H` of the slots whose state the
step updates (`shapes_ssm_hybrid.ssm_step_bytes` from the engine's
`state_slots`; the kernel also moves the idle slots', which nobody needs):
that over the chip's bytes/s, as a share of the kernel's device time. Bound:
memory. A program without the kernel, or a capture in which it did not run,
gives None."""

from chipbench import chunk_counters, shapes_ssm_hybrid, trace_reduce

KERNEL = "ssm_step"


def read(context):
    cell, reduced = context["cell"], context["trace"]
    pattern = cell.spec["modules"].get("ssm_hybrid_decode")
    slots = chunk_counters.mean(context, "state_slots")
    if pattern is None or slots is None:
        return None
    _, chunks = trace_reduce.module_seconds(reduced, pattern)
    kernel_s = sum(seconds for name, seconds in reduced["device_ops"] if name.startswith(KERNEL))
    if not chunks or not kernel_s:
        return None
    # every layer's H twice, once a decode step: all of a step's calls together
    need = shapes_ssm_hybrid.ssm_step_bytes(cell.config, slots) * chunks * context["chunk_size"]
    return need / context["peaks"]["hbm_bytes_per_s"] / kernel_s * 100.0
