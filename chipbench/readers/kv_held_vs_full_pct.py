"""Values the engine's pool holds a token a layer (`kv_row_values` of
`serve.decode_chunk`, read off the live pool's shapes) over what a cache of
decompressed keys and values of full heads would hold
(`shapes_latent_moe.full_head_kv_values`): 12.5 for a latent row of 576 values
stored in 640 (zero-padded to whole 128-lane tiles) against 16 heads of 192 +
128; 11.25 if a pool ever stores the 576 alone. The guard against a change
that caches decompressed K and V."""

from chipbench import chunk_counters, shapes_latent_moe


def read(context):
    counted = chunk_counters.chunks(context, ("kv_row_values",))
    if not counted:
        return None
    return counted[-1]["kv_row_values"] / shapes_latent_moe.full_head_kv_values(context["cell"].config) * 100.0
