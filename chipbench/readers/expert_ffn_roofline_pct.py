"""The routed experts' grouped matmuls against their memory roofline, over every
call of the kernel in the capture: the program's Pallas grouped matmul is the
operation `gmm` (XLA's own `ragged_dot` kernel, which a build without it runs,
is `ragged-dot`), three calls a layer, and the reduction keeps device time by
operation name for the whole capture, so the decode steps' calls and the
inserts' are in one sum. Each of them has to read, in every expert layer, the
matrices of the experts some row chose (`shapes_latent_moe.expert_ffn_bytes`
from the engine's `experts_touched` of the decode chunks): that over the
chip's bytes/s, as a share of the kernel's device time a call of its program.
Bound: memory — a decode step gives an expert a dozen rows, an insert at most a
few hundred.

An insert is charged the decode chunks' count too, which holds where an insert
sends the router at least a decode step's picks (`num_slots` rows): a bucket of
128 tokens or more in the accepted cell, 94% of its inserts and 99% of the
kernel's calls. A smaller bucket may leave experts untouched that the sum still
charges, so on a mix of short prompts and few slots the share reads too high
(PERF.md 7): the engine counts no touched experts an insert yet."""

from chipbench import chunk_counters, harness, shapes_latent_moe, trace_reduce

KERNELS = ("gmm", "ragged-dot")


def read(context):
    cell, reduced = context["cell"], context["trace"]
    touched = chunk_counters.mean(context, "experts_touched")
    pattern = cell.spec["modules"].get("latent_decode")
    if pattern is None or touched is None:
        return None
    _, chunks = trace_reduce.module_seconds(reduced, pattern)
    _, inserts = trace_reduce.module_seconds(reduced, cell.spec["modules"]["insert"])
    kernel_s = sum(seconds for name, seconds in reduced["device_ops"] if name.startswith(KERNELS))
    if not chunks or not kernel_s:
        return None
    counts = harness.load_module("reference", cell.config["family"], cell.root).param_counts(cell.config)
    need = shapes_latent_moe.expert_ffn_bytes(cell.config, counts, cell.spec["dtype"], touched)
    calls = chunks * context["chunk_size"] + inserts
    return need * calls / context["peaks"]["hbm_bytes_per_s"] / kernel_s * 100.0
