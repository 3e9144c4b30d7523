"""Bytes a decode step of the hybrid linear-attention family has to move, from a
configuration's published sizes: the yardstick of `hybrid_decode_roofline_pct`,
`delta_step_roofline_pct` and `state_and_kv_held_vs_full_pct`. `shapes.py`
counts a cache of keys and values in every layer; here only the
full-attention layers keep pages, and every linear-attention layer keeps a
fixed state a slot — a float32 matrix a head, read AND written once a step
whatever the context, and the short convolution's last inputs. Hand counts in
`tests/chipbench/` pin them. `counts` is `reference/olmo_hybrid.param_counts(c)`."""

from __future__ import annotations

from chipbench.shapes import DTYPE_BYTES

LINEAR, FULL = "linear_attention", "full_attention"


def recurrent_state_bytes_per_slot(c: dict) -> int:
    """`S` of one request, every linear layer's: heads x key size x value size, float32."""
    per_layer = c["linear_num_value_heads"] * c["linear_key_head_dim"] * c["linear_value_head_dim"]
    return c["layer_types"].count(LINEAR) * per_layer * DTYPE_BYTES["float32"]


def conv_state_bytes_per_slot(c: dict, dtype: str) -> int:
    """The convolution's last `taps - 1` inputs of one request, every linear layer's."""
    channels = c["linear_num_value_heads"] * (2 * c["linear_key_head_dim"] + c["linear_value_head_dim"])
    return c["layer_types"].count(LINEAR) * (c["linear_conv_kernel_dim"] - 1) * channels * DTYPE_BYTES[dtype]


def state_bytes_per_slot(c: dict, dtype: str) -> int:
    """What a busy slot holds whatever its length."""
    return recurrent_state_bytes_per_slot(c) + conv_state_bytes_per_slot(c, dtype)


def kv_bytes_per_token(c: dict, dtype: str) -> int:
    """Keys and values of one token in the full-attention layers' pages."""
    return c["layer_types"].count(FULL) * 2 * c["hidden_size"] * DTYPE_BYTES[dtype]


def full_attention_kv_bytes_per_token(c: dict, dtype: str) -> int:
    """What one token would hold if every layer were full attention."""
    return len(c["layer_types"]) * 2 * c["hidden_size"] * DTYPE_BYTES[dtype]


def decode_step_weight_bytes(counts: dict, dtype: str) -> int:
    """Every weight a decode step reads once: all but the embedding table,
    whose gathered rows are negligible."""
    return (counts["total"] - counts["embedding"]) * DTYPE_BYTES[dtype]


def delta_step_bytes(c: dict, state_slots: float) -> float:
    """`S` of the active slots, read and written once by every linear layer's update."""
    return 2.0 * state_slots * recurrent_state_bytes_per_slot(c)


def decode_step_bytes(c: dict, counts: dict, dtype: str, state_slots: float, live_tokens: float) -> float:
    """Weights outside the embedding table, the active slots' `S` twice (read
    and write), their convolution inputs, and the live tokens' pages."""
    return (decode_step_weight_bytes(counts, dtype) + delta_step_bytes(c, state_slots)
            + state_slots * conv_state_bytes_per_slot(c, dtype) + live_tokens * kv_bytes_per_token(c, dtype))

