"""What dropless routing pays under imbalance: the window's mean, over its
decode chunks, of the busiest routed expert's tokens over the mean expert's
(`expert_tokens_max` / `expert_tokens_mean` of `serve.decode_chunk`: layers
averaged, every row the chunk ran). 1 is an even spread; the grouped matmuls
take as long as the busiest expert's rows."""

from chipbench import chunk_counters


def read(context):
    counted = chunk_counters.chunks(context, ("expert_tokens_max", "expert_tokens_mean"))
    ratios = [a["expert_tokens_max"] / a["expert_tokens_mean"] for a in counted or () if a["expert_tokens_mean"]]
    return sum(ratios) / len(ratios) if ratios else None
