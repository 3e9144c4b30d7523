"""The residual streams' mixes against their memory roofline: the program's two
Pallas kernels are the operations `hc_pre` and `hc_post`, one call each a
sub-layer of every insert and of every decode step. A sub-layer has to move
`(3n + 2) C` values for every NEEDED row (`shapes_latent_moe_hc.mix_bytes_per_row`:
100,352 B): the real rows of an insert (`suffix_tokens`, not its bucket) and
the busy slots of a chunk's steps (`hc_rows` already counts those), each times
the sub-layers — the mean over the spans that start in the captured stretch,
times the modules' runs in the capture. That over the chip's bytes/s, as a
share of the two kernels' device time. Bound: memory by its bytes — `hc_pre`
also multiplies every row by `Phi` three times over on the matrix unit, so the
share stays under 100. A program without the kernels or the counters (the
parent of PR 40; a capture in which they did not run) gives None."""

from chipbench import hc_spans, shapes_latent_moe_hc, trace_reduce


def read(context):
    cell, reduced = context["cell"], context["trace"]
    kernel_s = hc_spans.mix_seconds(reduced)
    inserts, chunks = hc_spans.spans(context, "serve.insert"), hc_spans.spans(context, "serve.decode_chunk")
    if not kernel_s or inserts is None or chunks is None:
        return None
    _, insert_runs = trace_reduce.module_seconds(reduced, cell.spec["modules"]["insert"])
    _, chunk_runs = trace_reduce.module_seconds(reduced, cell.spec["modules"]["latent_hc_decode"])
    sublayers = shapes_latent_moe_hc.sublayers(cell.config)
    rows = (sum(a["suffix_tokens"] for a in inserts) / len(inserts) * sublayers * insert_runs
            + sum(a["hc_rows"] for a in chunks) / len(chunks) * chunk_runs)
    need = rows * shapes_latent_moe_hc.mix_bytes_per_row(cell.config, cell.spec["dtype"])
    return need / context["peaks"]["hbm_bytes_per_s"] / kernel_s * 100.0
