"""Bytes a decode step of the parallel state-space and attention family has to
move, from a configuration's published sizes: the yardstick of
`ssm_hybrid_decode_roofline_pct`, `ssm_step_roofline_pct` and
`state_held_vs_published_pct`. `shapes_hybrid.py` counts a family in which a
layer keeps pages OR a state; here EVERY layer keeps both — a float32 matrix a
mixer head, read AND written once a step whatever the context, the short
convolution's last inputs, and keys and values of `num_key_value_heads` heads a
token in pages. Hand counts in `tests/chipbench/` pin them. `counts` is
`reference/falcon_h1.param_counts(c)`."""

from __future__ import annotations

from chipbench.shapes import DTYPE_BYTES
from chipbench.shapes_hybrid import decode_step_weight_bytes  # noqa: F401 — every weight but the embedding table, once


def recurrent_state_bytes_per_slot(c: dict) -> int:
    """`H` of one request, every layer's: heads x head size x state size, float32."""
    return c["num_hidden_layers"] * c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"] * DTYPE_BYTES["float32"]


def conv_state_bytes_per_slot(c: dict, dtype: str) -> int:
    """The convolution's last `mamba_d_conv - 1` inputs of one request, every layer's."""
    channels = c["mamba_d_ssm"] + 2 * c["mamba_n_groups"] * c["mamba_d_state"]
    return c["num_hidden_layers"] * (c["mamba_d_conv"] - 1) * channels * DTYPE_BYTES[dtype]


def state_bytes_per_slot(c: dict, dtype: str) -> int:
    """What a busy slot holds whatever its length, as published: nothing padded or widened."""
    return recurrent_state_bytes_per_slot(c) + conv_state_bytes_per_slot(c, dtype)


def kv_bytes_per_token(c: dict, dtype: str) -> int:
    """Keys and values of one token in every layer's pages: the KV heads, not the query heads."""
    return c["num_hidden_layers"] * 2 * c["num_key_value_heads"] * c["head_dim"] * DTYPE_BYTES[dtype]


def ssm_step_bytes(c: dict, state_slots: float) -> float:
    """`H` of the active slots, read and written once by every layer's update."""
    return 2.0 * state_slots * recurrent_state_bytes_per_slot(c)


def decode_step_bytes(c: dict, counts: dict, dtype: str, state_slots: float, live_tokens: float) -> float:
    """Weights outside the embedding table, the active slots' `H` twice (read
    and write), their convolution inputs, and the live tokens' pages."""
    return (decode_step_weight_bytes(counts, dtype) + ssm_step_bytes(c, state_slots)
            + state_slots * conv_state_bytes_per_slot(c, dtype) + live_tokens * kv_bytes_per_token(c, dtype))
