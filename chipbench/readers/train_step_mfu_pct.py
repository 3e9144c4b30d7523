"""Model-FLOP/s utilization of the fused step while it runs on the device: the
step's model FLOPs (`shapes.train_step_flops`: forward and backward,
recomputation not counted) over the trace's device time of the step module a
run, the chip's bf16 peak and the chips. The end-to-end utilization is lower by
the device's idle share."""

from chipbench import harness, shapes, trace_reduce


def read(context):
    cell, reduced = context["cell"], context["trace"]
    seconds, runs = trace_reduce.module_seconds(reduced, cell.spec["modules"]["step"])
    if not runs:
        return None
    counts = harness.load_module("reference", cell.config["family"], cell.root).param_counts(cell.config)
    flops = shapes.train_step_flops(cell.config, counts, context["batch"], context["seq_len"])
    peak = context["peaks"]["bf16_flops_per_s"] * context["chips"]
    return flops / (seconds / runs) / peak * 100.0
