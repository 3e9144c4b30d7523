"""The latent read itself against its memory roofline: since PR 39 the engine
reads a pool of latent rows on a TPU through the program's Pallas page-walk
kernel, the operation `paged_attention`, one call a layer a decode step (an
insert does not call it: prefill reads the dense cache). A decode step has to
read the live tokens' latent rows once a layer
(`shapes_latent_moe.latent_read_bytes`, the published row of `kv_lora_rank +
qk_rope_head_dim` values; the live tokens are the client loop's own count at
each captured step): that, over the captured chunks' decode steps and the
chip's bytes/s, as a share of the kernel's device time. Bound: memory by its
bytes — the kernel copies whole pages of rows padded to whole lanes, and both
its products load a run into the matrix unit, so the share stays under 100.
A program that reads the pool with XLA's loop (the parent of PR 39, whose
read shares its operations' names with the dense layers'), or a capture in
which the kernel did not run, gives None."""

from chipbench import shapes_latent_moe, trace_reduce

KERNEL = "paged_attention"


def read(context):
    cell, reduced = context["cell"], context["trace"]
    pattern = cell.spec["modules"].get("latent_decode")
    if pattern is None:
        return None
    _, chunks = trace_reduce.module_seconds(reduced, pattern)
    kernel_s = sum(seconds for name, seconds in reduced["device_ops"] if name.startswith(KERNEL))
    start, stop = context["trace_span"]
    live = [s[4] for s in context["window"]["steps"] if start <= s[0] and s[1] <= stop]
    if not chunks or not kernel_s or not live:
        return None
    step_bytes = shapes_latent_moe.latent_read_bytes(cell.config, cell.spec["dtype"], sum(live) / len(live))
    return step_bytes * chunks * context["chunk_size"] / context["peaks"]["hbm_bytes_per_s"] / kernel_s * 100.0
