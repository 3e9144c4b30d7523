"""Kimi-VL-A3B's language model (the DeepSeek-V3 layer: latent attention, sigmoid-
routed experts beside a shared expert) in plain `jax.numpy`: the seeded weights
and the float32 forward pass that decides `correct` for the cells that serve it.

Per layer, pre-norm residuals, RMSNorm eps from the config, no biases:

  attention (MLA, `q_lora_rank` null)   h = RMSNorm(x); q = h W_q -> heads of
      [q_nope | q_pe]; [c | k_pe] = h W_kva; c = RMSNorm(c); RoPE on q_pe and on
      k_pe (one k_pe for all heads); [k_nope | v] = c W_kvb a head; scores
      (q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope), causal softmax,
      probs . v, W_o. ALWAYS this decompressed form: the absorbed form is the
      program's, and this file is what it is held against.
  layer < first_k_dense_replace         SwiGLU of `intermediate_size`.
  later layers                          s = sigmoid(h W_g) in float32; the
      chosen experts are the top `num_experts_per_tok` of s + b (b chooses
      only); w = s[chosen] / sum(s[chosen]) * routed_scaling_factor; EVERY
      expert is applied to every token and weighted by its gate, zero where
      not chosen; plus the shared expert of `n_shared_experts` widths.

No cache, no batching tricks, no kernels, nothing imported from the program.
Departures from the published code are the configuration file's `assumed`:
seeded weights, a seeded non-zero `e_score_correction_bias`, half-split RoPE
pairs, softmax scale without `mscale` (`rope_scaling` is null).

Weights are a nested dict, `{"params": {"embed_tokens": {"embedding"},
"layer_<i>": {"input_norm", "post_attn_norm": {"scale"}, "attention": {"wq",
"wkv_a", "wkv_b", "wo": {"kernel"}, "kv_norm": {"scale"}}, "mlp": {"w_gate",
"w_up", "w_down": {"kernel"}} | "moe": {"router": {"kernel"}, "router_bias",
"experts": {"w_gate", "w_up", "w_down": {"kernel" [E, in, out]}}, "shared":
{...}}}, "final_norm", "lm_head": {"kernel"}}}`, kernels `[in, out]`. They stay
in the type they are served in; the forward pass upcasts one layer, and inside
it one expert, at a time: at the published widths float32 weights are 21.7 GB.
"""

from __future__ import annotations

import functools
import json
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

#: A router margin (sixth against seventh biased score) under these counts as a
#: near-tie in the check's log line: bfloat16 resolves scores near 0.5 to 2e-3.
NEAR_TIE_MARGINS = (1e-3, 1e-2)
#: The least margins the check's second log line tries (`served_token_gaps`):
#: what `mean_gap` would read if only positions with every layer's margin at or
#: over each were held. The configuration file's `check.router_margin` is the
#: one that counts.
TRIED_MARGINS = (0.0, 1e-3, 2e-3, 3e-3, 5e-3, 1e-2)


def param_counts(c: dict) -> dict:
    """Parameter counts by part, and of the whole model as `c` cuts it."""
    h, v = c["hidden_size"], c["vocab_size"]
    heads, nope, rope = c["num_attention_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    rank, vd, f = c["kv_lora_rank"], c["v_head_dim"], c["moe_intermediate_size"]
    attention = h * heads * (nope + rope) + h * (rank + rope) + rank * heads * (nope + vd) + heads * vd * h
    norms = 2 * h + rank  # a layer's two RMSNorms and the latent's
    shared = 3 * h * c["n_shared_experts"] * f
    router = h * c["n_routed_experts"] + c["n_routed_experts"]
    expert = 3 * h * f
    dense_layer = attention + norms + 3 * h * c["intermediate_size"]
    expert_layer = attention + norms + shared + router + c["n_routed_experts"] * expert
    n_dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
    n_expert = c["num_hidden_layers"] - n_dense
    layers = n_dense * dense_layer + n_expert * expert_layer
    return {
        "embedding": v * h, "head": h * v, "final_norm": h,
        "attention": attention, "shared_expert": shared, "router": router, "routed_expert": expert,
        "outside_routed_experts": attention + norms + shared + router,
        "dense_layer": dense_layer, "expert_layer": expert_layer,
        "dense_layers": n_dense, "expert_layers": n_expert, "layers": layers,
        "total": 2 * v * h + h + layers,
    }


class _Sizes(NamedTuple):
    """The numbers `init_params` and the forward pass need, hashable for jit."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    n_shared_experts: int
    n_routed_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    first_k_dense_replace: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_norm_eps: float
    init_std: float
    router_bias_std: float

    @classmethod
    def of(cls, config: dict) -> "_Sizes":
        init = config.get("init", {})
        flat = dict(config, init_std=init.get("std", 0.02), router_bias_std=init.get("router_bias_std", 0.05))
        return cls(*(flat[f] for f in cls._fields))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _normal(key, shape: tuple, std: float, dtype: str, one_plus: bool = False):
    x = jax.random.normal(key, shape, jnp.float32) * std
    return (1.0 + x if one_plus else x).astype(jnp.dtype(dtype))


def init_params(config: dict, key, dtype=jnp.bfloat16):
    """Every weight from `key`, in the type it is served in, as HOST arrays:
    normal(0, `init.std`) kernels and embeddings, RMSNorm scales 1 + normal,
    the router's choice bias normal(0, `init.router_bias_std`) in float32 (see
    the configuration file's `assumed`). Made on the device one leaf a call
    (the whole model in one program would hold float32 temporaries of several
    stacks of experts beside 10.9 GB of weights) and fetched at once: the
    caller keeps this copy for the check while the program holds its own on
    the device, as a server that loaded a checkpoint does — so a program that
    holds the weights in another form (int8: the cell's control) fits beside
    nothing. `served_token_gaps` places them again once the program is gone."""
    s = _Sizes.of(config)
    dtype = jnp.dtype(dtype).name
    h, f = s.hidden_size, s.moe_intermediate_size
    heads, nope, rope, rank, vd = (s.num_attention_heads, s.qk_nope_head_dim, s.qk_rope_head_dim,
                                   s.kv_lora_rank, s.v_head_dim)
    counter = iter(range(1 << 20))

    def normal(*shape, std=s.init_std, dtype=dtype, one_plus=False):
        return np.asarray(_normal(jax.random.fold_in(key, next(counter)), shape, std, dtype, one_plus))

    def kernel(*shape):
        return {"kernel": normal(*shape)}

    def norm(n):
        return {"scale": normal(n, one_plus=True)}

    def swiglu(width):
        return {"w_gate": kernel(h, width), "w_up": kernel(h, width), "w_down": kernel(width, h)}

    params = {"embed_tokens": {"embedding": normal(s.vocab_size, h)}}
    for i in range(s.num_hidden_layers):
        layer = {
            "input_norm": norm(h), "post_attn_norm": norm(h),
            "attention": {"wq": kernel(h, heads * (nope + rope)), "wkv_a": kernel(h, rank + rope),
                          "kv_norm": norm(rank), "wkv_b": kernel(rank, heads * (nope + vd)),
                          "wo": kernel(heads * vd, h)},
        }
        if i < s.first_k_dense_replace:
            layer["mlp"] = swiglu(s.intermediate_size)
        else:
            e = s.n_routed_experts
            layer["moe"] = {
                "router": kernel(h, e),
                "router_bias": normal(e, std=s.router_bias_std, dtype="float32"),
                "experts": {"w_gate": kernel(e, h, f), "w_up": kernel(e, h, f), "w_down": kernel(e, f, h)},
                "shared": swiglu(s.n_shared_experts * f),
            }
        params[f"layer_{i}"] = layer
    params["final_norm"] = norm(h)
    params["lm_head"] = kernel(h, s.vocab_size)
    return {"params": params}


# --------------------------------------------------------------- the forward pass
def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def rms_norm(x, p, eps):
    x32 = x.astype(jnp.float32)
    normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps) * p["scale"].astype(jnp.float32)
    return normed.astype(x.dtype)


def rotary(x, positions, base: float):
    """x [b, t, heads, d]: every dim rotated, dimension i paired with i + d/2."""
    d = x.shape[-1]
    inv_freq = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[:, :, None, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., : d // 2].astype(jnp.float32), x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def swiglu(x, p):
    return (jax.nn.silu(x @ p["w_gate"]["kernel"]) * (x @ p["w_up"]["kernel"])) @ p["w_down"]["kernel"]


def attention(p, x, s: _Sizes):
    b, t, _ = x.shape
    heads, nope, rope, rank, vd = (s.num_attention_heads, s.qk_nope_head_dim, s.qk_rope_head_dim,
                                   s.kv_lora_rank, s.v_head_dim)
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    q = (x @ p["wq"]["kernel"]).reshape(b, t, heads, nope + rope)
    q_nope, q_pe = q[..., :nope], rotary(q[..., nope:], positions, s.rope_theta)
    row = x @ p["wkv_a"]["kernel"]
    c = rms_norm(row[..., :rank], p["kv_norm"], s.rms_norm_eps)
    k_pe = rotary(row[:, :, None, rank:], positions, s.rope_theta)  # [b, t, 1, rope]
    kv = (c @ p["wkv_b"]["kernel"]).reshape(b, t, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
              + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe[:, :, 0])).astype(jnp.float32) / math.sqrt(nope + rope)
    causal = jnp.tril(jnp.ones((t, t), bool))
    weights = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(b, t, heads * vd)
    return out @ p["wo"]["kernel"]


def route(p, x, s: _Sizes):
    """x [n, hidden] -> (gates [n, E]: the weight of each chosen expert, zero
    elsewhere; margin [n]: the last chosen biased score less the first not
    chosen, which a lower precision flips where it is small)."""
    scores = jax.nn.sigmoid(x.astype(jnp.float32) @ p["router"]["kernel"].astype(jnp.float32))
    biased = scores + p["router_bias"].astype(jnp.float32)[None, :]
    k = s.num_experts_per_tok
    ranked, ids = jax.lax.top_k(biased, k + 1)
    chosen = jnp.take_along_axis(scores, ids[:, :k], axis=-1)
    if s.norm_topk_prob:
        chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    chosen = chosen * s.routed_scaling_factor
    gates = jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], ids[:, :k]].set(chosen)
    return gates, ranked[:, k - 1] - ranked[:, k]


def experts(p, x, s: _Sizes):
    """`sum_e gate_e E_e(x) + S(x)`, every expert on every token; the stacks
    stay in their stored type and one expert's matrices are upcast at a time."""
    gates, margin = route(p, x, s)
    stacks = [p["experts"][name]["kernel"] for name in ("w_gate", "w_up", "w_down")]

    def one(total, expert):
        *matrices, gate = expert
        w_gate, w_up, w_down = (w.astype(jnp.float32) for w in matrices)
        y = (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down
        return total + gate[:, None] * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32), (*stacks, gates.T))
    return routed + swiglu(x, _f32(p["shared"])), margin


@functools.partial(jax.jit, static_argnums=(2,))
def block(p, x, s: _Sizes):
    """One layer, float32 at matmul precision "highest". x [b, t, hidden] ->
    (x, the router's margins [b * t], or None for a dense layer)."""
    with jax.default_matmul_precision("highest"):
        b, t, h = x.shape
        small = _f32({k: v for k, v in p.items() if k != "moe"})
        x = x + attention(small["attention"], rms_norm(x, small["input_norm"], s.rms_norm_eps), s)
        normed = rms_norm(x, small["post_attn_norm"], s.rms_norm_eps)
        if "moe" not in p:
            return x + swiglu(normed, small["mlp"]), None
        y, margin = experts(p["moe"], normed.reshape(b * t, h), s)
        return x + y.reshape(b, t, h), margin


def _logits(final_norm, lm_head, x, s: _Sizes):
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, final_norm, s.rms_norm_eps)
        return x @ lm_head["kernel"].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(3,))
def head_gaps(final_norm, lm_head, x, s: _Sizes, tokens):
    """For each row of x [b, n, hidden]: how far the logit of `tokens` [b, n]
    lies below the best logit. 0 where the token is the reference's own choice."""
    logits = _logits(final_norm, lm_head, x, s)
    chosen = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    return logits.max(-1) - chosen


def hidden_states(params, config: dict, ids, margins: list | None = None):
    """ids [b, t] -> the last layer's output [b, t, hidden], layer by layer so
    that only one layer's float32 copy is alive at a time. `margins` collects
    each expert layer's router margins [b, t]."""
    s = _Sizes.of(config)
    inner = params["params"]
    x = jnp.asarray(inner["embed_tokens"]["embedding"])[ids].astype(jnp.float32)
    for i in range(s.num_hidden_layers):
        x, margin = block(inner[f"layer_{i}"], x, s)
        if margins is not None and margin is not None:
            margins.append(margin.reshape(ids.shape))
    return x


def logits(params, config: dict, ids):
    """Full logits [b, t, vocab]; for tests at small sizes."""
    s = _Sizes.of(config)
    x = hidden_states(params, config, ids)
    return _logits(params["params"]["final_norm"], params["params"]["lm_head"], x, s)


def served_token_gaps(params, config: dict, served: list, pad_to: int, rows: int, batch: int = 2) -> list:
    """Teacher-forced check of served requests. `served` is a list of
    `(prompt_ids, generated_tokens)`; each is run once through the reference as
    prompt + generated[:-1], right-padded to `pad_to` (causal: pads are never
    seen), and a generated token is held against the reference's best logit
    at its position. Returns one float array of gaps a request. `rows` bounds
    the generated tokens of one request (the head is computed on that many
    positions).

    Which tokens are held. The program routes from bfloat16 hidden states, so
    where the reference's sixth and seventh biased scores lie closer than
    bfloat16 tells apart its sixth expert may be the other one: a different
    sum of experts at that token, a gap of tenths with seeded experts, and no
    fault. A token is therefore held only where EVERY expert layer's margin at
    its position is at least the configuration file's `check.router_margin` —
    the reference's own float32 margins, nothing of the program's — so that
    what is read is the path's error and not the count of such coin-flips (a
    token's context still holds rows of positions that did flip). 0 or no
    such key holds every token.

    Also prints two JSON lines over the real positions: how many router choices
    were near-ties (`NEAR_TIE_MARGINS`), and what the held tokens would read at
    each of `TRIED_MARGINS` (how many, their mean and largest gap), which is
    what the shipped margin was chosen from."""
    s = _Sizes.of(config)
    least_margin = float(config.get("check", {}).get("router_margin", 0.0))
    inner = jax.device_put(params["params"])  # once, after the program has gone: they come as host arrays
    params = {"params": inner}
    out = []
    routed, near = 0, [0] * len(NEAR_TIE_MARGINS)
    all_gaps, all_margins = [], []
    for start in range(0, len(served), batch):
        group = served[start:start + batch]
        ids = np.zeros((len(group), pad_to), np.int32)
        tokens = np.zeros((len(group), rows), np.int32)
        first = np.zeros((len(group),), np.int32)
        real = np.zeros((len(group), pad_to), bool)
        for j, (prompt, generated) in enumerate(group):
            n = len(generated)
            if n > rows or len(prompt) + n - 1 > pad_to:
                raise ValueError("a served request is longer than the reference was sized for")
            ids[j, : len(prompt)] = prompt
            ids[j, len(prompt): len(prompt) + n - 1] = generated[:-1]
            tokens[j, :n] = generated
            first[j] = len(prompt) - 1
            real[j, : len(prompt) + n - 1] = True
        margins: list = []
        x = hidden_states(params, config, jnp.asarray(ids), margins)
        margins = jax.device_get(margins)
        for margin in margins:
            routed += int(real.sum())
            for m, limit in enumerate(NEAR_TIE_MARGINS):
                near[m] += int((margin[real] < limit).sum())
        # the least margin over the expert layers at each position; a model without experts has none
        least = np.min(np.stack(margins), axis=0) if margins else np.full(ids.shape, np.inf, np.float32)
        index = jnp.minimum(jnp.asarray(first)[:, None] + jnp.arange(rows)[None, :], pad_to - 1)
        x = jnp.take_along_axis(x, index[..., None], axis=1)
        gaps = np.asarray(jax.device_get(
            head_gaps(inner["final_norm"], inner["lm_head"], x, s, jnp.asarray(tokens))))
        for j, (_prompt, generated) in enumerate(group):
            mine, at = gaps[j, : len(generated)], least[j, first[j]: first[j] + len(generated)]
            all_gaps.append(mine)
            all_margins.append(at)
            out.append(mine[at >= least_margin])
    print(json.dumps({"check": "router near-ties in the reference", "routed_positions": routed,
                      **{f"margin_under_{limit:g}": n for limit, n in zip(NEAR_TIE_MARGINS, near)}}),
          flush=True)
    all_gaps, all_margins = np.concatenate(all_gaps), np.concatenate(all_margins)
    tried = {}
    for limit in TRIED_MARGINS:
        held = all_gaps[all_margins >= limit]
        tried[f"{limit:g}"] = {"tokens": int(held.size), "mean_gap": float(held.mean()) if held.size else None,
                               "max_gap": float(held.max()) if held.size else None}
    print(json.dumps({"check": "tokens held by least router margin", "router_margin": least_margin, **tried}),
          flush=True)
    return out
