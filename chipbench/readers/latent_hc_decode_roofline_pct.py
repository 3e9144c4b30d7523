"""The decode chunk of the latent-attention, sparse-expert family with low-rank
queries and a residual path of several streams against its memory roofline: as
`latent_moe_decode_roofline_pct`, from `shapes_latent_moe_hc.decode_step_bytes`
(both factors of the queries and the float32 maps counted) — the bytes one
decode step has to read over the chip's bytes/s, as a share of the trace's
device time of the decode-chunk module a step. Bound: memory. The live tokens
are the client loop's own count at each traced step."""

from chipbench import chunk_counters, harness, shapes_latent_moe_hc, trace_reduce


def read(context):
    cell, reduced = context["cell"], context["trace"]
    pattern = cell.spec["modules"].get("latent_hc_decode")
    touched = chunk_counters.mean(context, "experts_touched")
    if pattern is None or touched is None:
        return None
    seconds, runs = trace_reduce.module_seconds(reduced, pattern)
    start, stop = context["trace_span"]
    live = [s[4] for s in context["window"]["steps"] if start <= s[0] and s[1] <= stop]
    if not runs or not live:
        return None
    counts = harness.load_module("reference", cell.config["family"], cell.root).param_counts(cell.config)
    need = shapes_latent_moe_hc.decode_step_bytes(cell.config, counts, cell.spec["dtype"], touched,
                                                  sum(live) / len(live))
    floor_s = need / context["peaks"]["hbm_bytes_per_s"]
    return floor_s / (seconds / runs / context["chunk_size"]) * 100.0
