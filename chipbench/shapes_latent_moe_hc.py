"""What a decode step of the latent-attention, sparse-expert family with
low-rank queries and a residual path of `n = hc_mult` streams has to read, what
an insert has to compute, and what the streams' mixes have to move, from a
configuration's published sizes: the yardstick of `latent_hc_prefill_mfu_pct`,
`latent_hc_decode_roofline_pct` and `hc_mix_roofline_pct`. Beside
`shapes_latent_moe.py` (full-rank queries, one stream), which it does not
change: here the queries' two factors and the maps are counted, the maps in
float32 as they are stored. Hand counts in `tests/chipbench/` pin them.
`counts` is `reference/latent_moe_hc.param_counts(c)`."""

from __future__ import annotations

from chipbench.shapes import DTYPE_BYTES
from chipbench.shapes_latent_moe import expert_ffn_bytes, kv_row_values  # the same counts: `[c | k_pe]`, the touched experts

#: The maps (`Phi`, the alphas, the biases) and the router's choice bias are float32 whatever the weights are.
MAP_BYTES = 4
#: The pool's row is zero-padded to whole lanes of this many values (576 -> 640).
LANES = 128


def stored_row_values(c: dict) -> int:
    """Values the pool stores a token a layer: the row padded to whole lanes."""
    return -(-kv_row_values(c) // LANES) * LANES


def kv_bytes_per_token(c: dict, dtype: str) -> int:
    """The STORED latent rows of one token over all layers (10,240 B at 8 layers)."""
    return c["num_hidden_layers"] * stored_row_values(c) * DTYPE_BYTES[dtype]


def latent_read_bytes(c: dict, dtype: str, live_tokens: float) -> float:
    """The live tokens' latent rows as published (576 values), every layer's read once a step."""
    return live_tokens * c["num_hidden_layers"] * kv_row_values(c) * DTYPE_BYTES[dtype]


def mix_bytes_per_row(c: dict, dtype: str) -> int:
    """What one sub-layer's two mixes have to move for one row: `hc_pre` reads
    the n streams and writes u, `hc_post` reads the n streams and y and writes
    n streams — `(3n + 2) C` values (100,352 B at n = 4, C = 3,584, bfloat16).
    The 24 maps of a row, 96 B, are not counted."""
    return (3 * c["hc_mult"] + 2) * c["hidden_size"] * DTYPE_BYTES[dtype]


def sublayers(c: dict) -> int:
    """Attention and feed-forward of every layer: each has its own mixes."""
    return 2 * c["num_hidden_layers"]


def float32_params(c: dict, counts: dict) -> int:
    """Parameters stored in float32: every sub-layer's maps and every router's choice bias."""
    return sublayers(c) * counts["maps"] + counts["expert_layers"] * c["n_routed_experts"]


def decode_step_weight_bytes(c: dict, counts: dict, dtype: str, experts_touched: float) -> float:
    """Every weight a decode step reads once: the dense layers, each expert
    layer's attention (both factors of the queries), maps, norms, shared expert
    and router, the touched routed experts, the final norm and the head; the
    float32 parameters at four bytes. The embedding's gathered rows are not counted."""
    outside = (counts["dense_layers"] * counts["dense_layer"]
               + counts["expert_layers"] * counts["outside_routed_experts"]
               + counts["final_norm"] + counts["head"])
    wide = float32_params(c, counts)
    return ((outside - wide) * DTYPE_BYTES[dtype] + wide * MAP_BYTES
            + expert_ffn_bytes(c, counts, dtype, experts_touched))


def decode_step_bytes(c: dict, counts: dict, dtype: str, experts_touched: float, live_tokens: float) -> float:
    return decode_step_weight_bytes(c, counts, dtype, experts_touched) + latent_read_bytes(c, dtype, live_tokens)


def matmul_params_per_row(c: dict, counts: dict) -> int:
    """The parameters every row of an insert is multiplied by: all matrices of
    the attention and the maps' `Phi` in every layer, the dense layers' SwiGLU,
    and in an expert layer the router, the shared expert and the
    `num_experts_per_tok` routed experts a row chooses. Norm scales, alphas
    and biases multiply nothing."""
    h, n = c["hidden_size"], c["hc_mult"]
    attention = counts["attention"] - c["q_lora_rank"] - c["kv_lora_rank"]  # less its two norms' scales
    phi = 2 * n * h * (2 * n + n * n)  # both sub-layers'
    dense = 3 * h * c["intermediate_size"]
    sparse = (h * c["n_routed_experts"] + counts["shared_expert"]
              + c["num_experts_per_tok"] * counts["routed_expert"])
    return (c["num_hidden_layers"] * (attention + phi)
            + counts["dense_layers"] * dense + counts["expert_layers"] * sparse)


def insert_flops(c: dict, counts: dict, rows: float) -> float:
    """The FLOPs an insert of `rows` real rows NEEDS: two a parameter a row
    (`matmul_params_per_row`), causal attention over the rows (row p reads p + 1
    keys of `qk_head_dim` and values of `v_head_dim`, every head, every layer),
    and the head for the ONE row that is sampled. Bucket padding, the head over
    every row of a bucket and the decompression of padded rows are what the
    program may run besides; they are not needed."""
    per_key = 2 * c["num_attention_heads"] * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])
    attention = c["num_hidden_layers"] * per_key * rows * (rows + 1) / 2
    return 2 * matmul_params_per_row(c, counts) * rows + attention + 2 * counts["head"]
