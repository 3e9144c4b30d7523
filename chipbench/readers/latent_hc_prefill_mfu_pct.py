"""The insert (prefill) program's model-FLOP/s utilization while it runs on the
device: the FLOPs the captured inserts NEED (`shapes_latent_moe_hc.insert_flops`
of each insert's `suffix_tokens` real rows — not its bucket — through the
layers' active parameters and causal attention, the head for the one sampled
row; the mean over the `serve.insert` spans that start in the captured stretch,
times the insert module's runs in the capture) over the trace's device time of
the insert module and the chip's bf16 peak. Bound: compute. Bucket padding,
the head over every row of a bucket and rows an expert's tile pads are device
time with no needed FLOPs, so they lower it; it cannot pass 100. In a
prompt-heavy cell most of the device's time is this module, so this is the
cell's share of the whole step's peak. A program whose inserts carry no
`hc_rows` (the parent of PR 40) gives None."""

from chipbench import harness, hc_spans, shapes_latent_moe_hc, trace_reduce


def read(context):
    cell, reduced = context["cell"], context["trace"]
    seconds, runs = trace_reduce.module_seconds(reduced, cell.spec["modules"]["insert"])
    inserts = hc_spans.spans(context, "serve.insert")
    if not runs or not seconds or inserts is None:
        return None
    counts = harness.load_module("reference", cell.config["family"], cell.root).param_counts(cell.config)
    flops = [shapes_latent_moe_hc.insert_flops(cell.config, counts, a["suffix_tokens"]) for a in inserts]
    return sum(flops) / len(flops) * runs / seconds / context["peaks"]["bf16_flops_per_s"] * 100.0
