"""Share of the device's busy time, in the traced window, that the residual
streams' mixes took: the operations named `hc_pre*` / `hc_post*` (the program's
two Pallas kernels) over every operation. None where they did not run."""

from chipbench import hc_spans


def read(context):
    reduced = context["trace"]
    kernel_s = hc_spans.mix_seconds(reduced)
    if not kernel_s or not reduced["busy_s"]:
        return None
    return kernel_s / reduced["busy_s"] * 100.0
