"""Bytes a decode step of the latent-attention, sparse-expert family has to
read, from a configuration's published sizes: the yardstick of the family's
roofline shares (`latent_moe_decode_roofline_pct`, `latent_read_roofline_pct`,
`expert_ffn_roofline_pct`) and of `kv_held_vs_full_pct`. `shapes.py` counts a
cache of full heads and every weight; here the cache is one latent row a token
a layer, and of the routed experts a step reads only those some token chose.
Hand counts in `tests/chipbench/` pin them. `counts` is
`reference/latent_moe.param_counts(c)`."""

from __future__ import annotations

from chipbench.shapes import DTYPE_BYTES


def kv_row_values(c: dict) -> int:
    """Values the cache holds a token a layer: `[c | k_pe]`."""
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def full_head_kv_values(c: dict) -> int:
    """Values a cache of decompressed keys and values would hold a token a
    layer: every head's key (`nope + rope`) and value."""
    return c["num_attention_heads"] * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])


def kv_bytes_per_token(c: dict, dtype: str) -> int:
    """The latent rows of one token over all layers."""
    return c["num_hidden_layers"] * kv_row_values(c) * DTYPE_BYTES[dtype]


def expert_ffn_bytes(c: dict, counts: dict, dtype: str, experts_touched: float) -> float:
    """The routed experts' matrices one decode step reads: in every expert
    layer, the `experts_touched` experts that at least one row of the step
    chose (all of them at any batch worth serving)."""
    return counts["expert_layers"] * experts_touched * counts["routed_expert"] * DTYPE_BYTES[dtype]


def decode_step_weight_bytes(c: dict, counts: dict, dtype: str, experts_touched: float) -> float:
    """Every weight a decode step reads once: the dense layers, each expert
    layer's attention, norms, shared expert and router, the touched routed
    experts, the final norm and the head. The embedding table's gathered rows
    are negligible and its full size is not counted."""
    outside = (counts["dense_layers"] * counts["dense_layer"]
               + counts["expert_layers"] * counts["outside_routed_experts"]
               + counts["final_norm"] + counts["head"])
    return outside * DTYPE_BYTES[dtype] + expert_ffn_bytes(c, counts, dtype, experts_touched)


def latent_read_bytes(c: dict, dtype: str, live_tokens: float) -> float:
    """The live tokens' latent rows, every layer's read once a step."""
    return live_tokens * kv_bytes_per_token(c, dtype)


def decode_step_bytes(c: dict, counts: dict, dtype: str, experts_touched: float, live_tokens: float) -> float:
    return decode_step_weight_bytes(c, counts, dtype, experts_touched) + latent_read_bytes(c, dtype, live_tokens)
