"""The decode chunk of a parallel state-space and attention model against its
memory roofline: the bytes one decode step has to move — every weight outside
the embedding table, the recurrent state `H` of the slots whose state the step
updates read AND written (the engine's `state_slots`), their convolution
inputs, and the live tokens' pages in every layer
(`shapes_ssm_hybrid.decode_step_bytes`) — over the chip's bytes/s, as a share
of the trace's device time of the decode-chunk module a step. Bound: memory.
The live tokens are the client loop's own count at each traced step. A program
whose chunks carry no `state_slots` (a parent commit) gives None."""

from chipbench import chunk_counters, harness, shapes_ssm_hybrid, trace_reduce


def read(context):
    cell, reduced = context["cell"], context["trace"]
    pattern = cell.spec["modules"].get("ssm_hybrid_decode")
    slots = chunk_counters.mean(context, "state_slots")
    if pattern is None or slots is None:
        return None
    seconds, runs = trace_reduce.module_seconds(reduced, pattern)
    start, stop = context["trace_span"]
    live = [s[4] for s in context["window"]["steps"] if start <= s[0] and s[1] <= stop]
    if not runs or not live:
        return None
    counts = harness.load_module("reference", cell.config["family"], cell.root).param_counts(cell.config)
    need = shapes_ssm_hybrid.decode_step_bytes(cell.config, counts, cell.spec["dtype"], slots, sum(live) / len(live))
    floor_s = need / context["peaks"]["hbm_bytes_per_s"]
    return floor_s / (seconds / runs / context["chunk_size"]) * 100.0
