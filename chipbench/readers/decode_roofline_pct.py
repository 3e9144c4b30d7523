"""The decode chunk against its memory roofline: the bytes one decode step has
to read (every weight outside the embedding table once, and the keys and values
of every live token; `shapes.decode_step_bytes`) over the chip's bytes/s, as a
share of the trace's device time of the decode-chunk module a step. Bound:
memory. The live tokens are the client loop's own count at each traced step."""

from chipbench import harness, shapes, trace_reduce


def read(context):
    cell, reduced = context["cell"], context["trace"]
    seconds, runs = trace_reduce.module_seconds(reduced, cell.spec["modules"]["decode"])
    if not runs:
        return None
    start, stop = context["trace_span"]
    live = [s[4] for s in context["window"]["steps"] if start <= s[0] and s[1] <= stop]
    if not live:
        return None
    counts = harness.load_module("reference", cell.config["family"], cell.root).param_counts(cell.config)
    weight_bytes = (counts["total"] - counts["embedding"]) * shapes.DTYPE_BYTES[cell.spec["dtype"]]
    need = shapes.decode_step_bytes(weight_bytes, sum(live) / len(live), context["kv_bytes_per_token"])
    floor_s = need / context["peaks"]["hbm_bytes_per_s"]
    return floor_s / (seconds / runs / context["chunk_size"]) * 100.0
