"""Operations and bytes a step needs, from a configuration's published sizes.
The yardstick for roofline and utilization shares: kept here, where a PR that
claims a gain cannot change it. Hand counts in `tests/chipbench/` pin them. A
family's parameter counts are its reference's (`reference/<family>.param_counts`)."""

from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def kv_bytes_per_token(c: dict, dtype: str) -> int:
    """Keys and values of one token over all layers (full multi-head cache)."""
    return 2 * c["num_hidden_layers"] * c["hidden_size"] * DTYPE_BYTES[dtype]


def decode_step_bytes(weight_bytes: int, live_tokens: float, kv_per_token: int) -> float:
    """Bytes one decode step has to read: every weight once (the embedding
    table's gathered rows are negligible and its full size is not counted), and
    the keys and values of every live token of the active slots."""
    return weight_bytes + live_tokens * kv_per_token


def train_step_flops(c: dict, counts: dict, batch: int, seq_len: int) -> float:
    """Model FLOPs of one optimizer step, forward and backward, recomputation
    not counted: 6 x (parameters outside the input embedding) a token, plus the
    attention scores and values, 12 x layers x hidden x seq a token. `counts`
    is the family's `reference/<family>.param_counts(c)`."""
    matmul_params = counts["total"] - counts["embedding"]
    per_token = 6 * matmul_params + 12 * c["num_hidden_layers"] * c["hidden_size"] * seq_len
    return float(per_token) * batch * seq_len
