"""Expert parallelism (EP): top-k routing + capacity-based einsum dispatch over an
"expert" mesh axis.

The reference has no in-tree MoE machinery — EP exists only as DeepSpeed-MoE
leaf-module passthrough (dataclasses.py:992-1010, commands/launch.py:499-505), with
routing/all-to-all delegated to DeepSpeed's CUDA kernels. Here EP is first-class and
TPU-native (SURVEY §2.5 "expert-axis sharding + all-to-all dispatch"): the GShard-style
dense dispatch/combine einsums are XLA's preferred MoE formulation — with expert-major
tensors sharded over the "expert" axis and tokens over "data", GSPMD lowers the
dispatch einsum to an all-to-all over ICI, exactly the comm pattern DeepSpeed implements
by hand.

Shapes (per jit program, global):  tokens T = B*S, experts E, capacity C, hidden H.
  dispatch [T, E, C] one-hot   — token t goes to slot c of expert e
  combine  [T, E, C] float     — same support, weighted by the renormalized router gate
  expert_in  = einsum('tec,th->ech', dispatch, x)     (all-to-all under GSPMD)
  expert_out = vmapped_ffn(expert_in)                 (fully expert-parallel)
  y          = einsum('tec,ech->th', combine, expert_out)  (all-to-all back)

`DroplessMoE` is the other formulation, for families whose routing may drop
nothing (sigmoid scores, a choice bias, a shared expert): the (token, expert)
pairs are sorted by expert and every expert multiplies exactly its own rows
(`grouped_matmul`: a Pallas kernel on a TPU), so the work follows the pairs,
not experts x capacity, at every token count. One device holds all its experts; an "expert" mesh axis for
it is not built. `MoEBlock` stays what `mixtral` trains through.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

# Appended to a model's TP rules: expert FFN kernels are [E, in, out]; dim 0 shards
# over "expert", the contraction dims keep Megatron column/row layout over "model".
EXPERT_SHARDING_RULES = [
    (r"experts/(w_gate|w_up)/kernel", ("expert", None, "model")),
    (r"experts/w_down/kernel", ("expert", "model", None)),
]


def expert_capacity(num_tokens: int, num_experts: int, top_k: int, capacity_factor: float) -> int:
    """Per-expert slot count: even share × top_k × slack (GShard capacity rule)."""
    return max(1, int(np.ceil(num_tokens * top_k / num_experts * capacity_factor)))


def top_k_routing(router_logits, top_k: int, capacity: int):
    """Compute dispatch/combine tensors for top-k token→expert routing.

    Args:
        router_logits: [T, E] raw router scores.
        top_k: experts per token.
        capacity: max tokens per expert; overflow tokens are dropped (their combine
            weight is zero — the residual connection carries them through unchanged).

    Returns:
        (dispatch [T,E,C] same-dtype one-hot, combine [T,E,C], aux) where aux is a dict
        with `load_balance_loss` (Switch-style E·Σ f_e·P_e) and `router_z_loss`.
    """
    T, E = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)  # [T, E]

    # top-k expert ids per token, processed in priority order so a token's k-th choice
    # only takes a slot after every token's (k-1)-th choice (GShard ordering).
    gate_vals, expert_ids = jax.lax.top_k(probs, top_k)  # [T, k]
    # renormalize the kept gates (Mixtral normalizes over the top-k set)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(axis=-1, keepdims=True), 1e-9)

    onehot = jax.nn.one_hot(expert_ids, E, dtype=jnp.float32)  # [T, k, E]

    # Slot assignment: within each (priority, expert), tokens take slots in order;
    # priorities stack — choice j starts after all slots used by choices < j.
    position_in_expert = jnp.zeros((T, top_k), dtype=jnp.int32)
    used = jnp.zeros((E,), dtype=jnp.float32)
    positions = []
    keep = []
    for j in range(top_k):
        oh = onehot[:, j, :]  # [T, E]
        pos_j = (jnp.cumsum(oh, axis=0) - 1.0) + used[None, :]  # [T, E] slot index
        pos_tok = jnp.sum(pos_j * oh, axis=-1)  # [T]
        within = pos_tok < capacity
        positions.append(pos_tok.astype(jnp.int32))
        keep.append(within)
        used = used + jnp.sum(oh, axis=0)
    position_in_expert = jnp.stack(positions, axis=1)  # [T, k]
    keep = jnp.stack(keep, axis=1)  # [T, k]

    slot_onehot = jax.nn.one_hot(position_in_expert, capacity, dtype=jnp.float32)  # [T,k,C]
    keep_f = keep.astype(jnp.float32)[..., None]  # [T,k,1]
    # [T,k,E,C] → reduce the k axis
    dispatch = jnp.einsum("tke,tkc->tec", onehot * keep_f, slot_onehot)
    combine = jnp.einsum("tke,tkc->tec", onehot * keep_f * gate_vals[..., None], slot_onehot)

    # aux losses (computed on ALL tokens' router probs, not just kept ones)
    # f_e: fraction of token-choices routed to e; P_e: mean router prob for e.
    f = jnp.mean(onehot.sum(axis=1), axis=0)  # [E]
    P = jnp.mean(probs, axis=0)  # [E]
    load_balance_loss = E * jnp.sum(f * P) / top_k
    z = jax.scipy.special.logsumexp(router_logits.astype(jnp.float32), axis=-1)
    router_z_loss = jnp.mean(jnp.square(z))
    aux = {"load_balance_loss": load_balance_loss, "router_z_loss": router_z_loss}
    return dispatch, combine, aux


class ExpertMLP(nn.Module):
    """SwiGLU FFN with a leading expert axis on every kernel ([E, ...], sharded over
    the "expert" mesh axis by EXPERT_SHARDING_RULES)."""

    hidden_size: int
    intermediate_size: int
    num_experts: int

    @nn.compact
    def __call__(self, x):  # x: [E, C, H]
        E, H, F = self.num_experts, self.hidden_size, self.intermediate_size
        init = nn.initializers.lecun_normal()
        w_gate = self.param("w_gate/kernel", lambda k, s: init(k, s), (E, H, F))
        w_up = self.param("w_up/kernel", lambda k, s: init(k, s), (E, H, F))
        w_down = self.param("w_down/kernel", lambda k, s: init(k, s), (E, F, H))
        gate = jnp.einsum("ech,ehf->ecf", x, w_gate)
        up = jnp.einsum("ech,ehf->ecf", x, w_up)
        return jnp.einsum("ecf,efh->ech", nn.silu(gate) * up, w_down)


class MoEBlock(nn.Module):
    """Router + expert-parallel FFN (the in-tree Mixtral/Switch FFN replacement for the
    reference's DeepSpeed-MoE passthrough)."""

    hidden_size: int
    intermediate_size: int
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25

    @nn.compact
    def __call__(self, hidden):  # [B, S, H]
        B, S, H = hidden.shape
        T = B * S
        x = hidden.reshape(T, H)
        router_logits = nn.Dense(self.num_experts, use_bias=False, name="router")(
            x.astype(jnp.float32)
        )
        C = expert_capacity(T, self.num_experts, self.top_k, self.capacity_factor)
        dispatch, combine, aux = top_k_routing(router_logits, self.top_k, C)
        dispatch = dispatch.astype(hidden.dtype)
        combine = combine.astype(jnp.float32)

        expert_in = jnp.einsum("tec,th->ech", dispatch, x)  # a2a under GSPMD
        expert_out = ExpertMLP(
            self.hidden_size, self.intermediate_size, self.num_experts, name="experts"
        )(expert_in)
        y = jnp.einsum("tec,ech->th", combine, expert_out.astype(jnp.float32))
        return y.reshape(B, S, H).astype(hidden.dtype), aux


# ------------------------------------------------------------- dropless routing
def sigmoid_top_k_routing(logits, choice_bias, top_k: int, scaling: float, normalize: bool = True):
    """DeepSeek-V3-style routing (`noaux_tc`, one group): scores are
    `sigmoid(logits)` in float32; the `top_k` experts are the largest of
    `scores + choice_bias` — the bias steers the CHOICE only — and the weights
    are the chosen experts' own scores, normalised to sum to one (`normalize`)
    and scaled. logits [T, E], choice_bias [E] -> (expert ids [T, k] int32,
    weights [T, k] float32). No capacity: every pair is kept."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, ids = jax.lax.top_k(scores + choice_bias.astype(jnp.float32)[None, :], top_k)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if normalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), weights * scaling


#: Tile of the Pallas grouped matmul: rows of a visit, columns of a weight block
#: (all of `K` rides in one block). One v5e, 64 experts of [2048, 1408] and
#: [1408, 2048], 768 / 1,536 / 6,144 rows (PERF.md §6, PR 31): 543 / 583 / 814 us
#: a call against a floor of 451 (every expert's matrix once at 819 GB/s) where
#: XLA's own `ragged_dot` kernel takes 1,922 / 2,883 / 3,332; 256 rows or
#: narrower blocks are slower, wider ones no faster.
_GMM_TILE_ROWS, _GMM_TILE_COLS = 128, 512


def _gmm(rows, kernels, group_sizes, interpret: bool = False):
    """The megablox Pallas kernel (`jax.experimental.pallas.ops.tpu.megablox`):
    a grid over (column block, visit, K block) where a visit is one expert's
    rows inside one tile of `_GMM_TILE_ROWS` rows, the expert's weight block
    streamed once a visit. Rows are padded to whole tiles; what lies past the
    last group is never visited and is cut off."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = rows.shape
    n = kernels.shape[2]
    pad = -m % _GMM_TILE_ROWS
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = gmm(rows, kernels, group_sizes, rows.dtype, (_GMM_TILE_ROWS, k, min(_GMM_TILE_COLS, n)),
              interpret=interpret)
    return out[:m]


def grouped_matmul(rows, kernels, group_sizes):
    """`rows[start_e:end_e] @ kernels[e]` for every expert e, rows sorted by
    expert: rows [M, K], kernels [E, K, N], group_sizes [E] (sum M) -> [M, N].
    On a TPU the Pallas grouped matmul (`_gmm`), which reads each expert's
    matrix once at four fifths of the chip's bandwidth; elsewhere
    `jax.lax.ragged_dot`, the same sum as XLA's plain loop."""
    group_sizes = group_sizes.astype(jnp.int32)
    if jax.default_backend() == "tpu":
        return _gmm(rows, kernels, group_sizes)
    return jax.lax.ragged_dot(rows, kernels, group_sizes)


def dropless_expert_ffn(x, expert_ids, weights, w_gate, w_up, w_down):
    """`y[t] = sum_j weights[t, j] * E_{ids[t, j]}(x[t])`, `E_e(z) = (silu(z W1_e)
    * z W3_e) W2_e`, with no token dropped at any count: the T*k (token,
    expert) pairs are sorted by expert (stable, so a token's order inside an
    expert is its own), each expert multiplies its own run of rows, and the
    results return to token order by the inverse permutation — a gather, no
    scatter-add. x [T, H]; kernels [E, H, F], [E, H, F], [E, F, H]. Returns
    (y [T, H] in x's dtype, tokens an expert [E] int32)."""
    T, k = expert_ids.shape
    E = w_gate.shape[0]
    flat = expert_ids.reshape(T * k)
    with jax.named_scope("moe_route"):
        order = jnp.argsort(flat, stable=True)
        inverse = jnp.zeros_like(order).at[order].set(jnp.arange(T * k, dtype=order.dtype))
        group_sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1)
        rows = jnp.take(x, order // k, axis=0)  # pair i is token i // k
    with jax.named_scope("moe_experts"):
        hidden = nn.silu(grouped_matmul(rows, w_gate, group_sizes)) * grouped_matmul(rows, w_up, group_sizes)
        out = grouped_matmul(hidden.astype(x.dtype), w_down, group_sizes)
    with jax.named_scope("moe_route"):
        out = jnp.take(out, inverse, axis=0).reshape(T, k, -1)
        y = jnp.sum(out.astype(jnp.float32) * weights[..., None], axis=1)
    return y.astype(x.dtype), group_sizes
