"""Xing4.0-29B-A4B's language model in plain `jax.numpy`: the DeepSeek-V3 layer
(latent attention, sigmoid-routed experts beside a shared expert; arXiv:2412.19437)
with low-rank queries, YaRN positions and a residual path of `n = hc_mult`
streams mixed by manifold-constrained hyper-connections (arXiv:2409.19606,
arXiv:2512.24880). The seeded weights and the float32 forward pass that decides
`correct` for the cells that serve it. `C` = hidden_size; RMSNorm eps
`rms_norm_eps`; no biases.

  streams      X_0 [t, n, C]: the embedding row repeated n times. After the last
      layer h = sum_i X[:, i, :], then the final RMSNorm and the untied head.
  a sub-layer F (attention, then feed-forward; each with its own maps)
      xt = vec(X_t) * rsqrt(mean(vec(X_t)^2) + hc_eps)        (no learned scale)
      [p | q | r] = xt . Phi,   Phi [nC, n + n + n^2]
      H_pre = sigmoid(a_pre p + b_pre); H_post = 2 sigmoid(a_post q + b_post)
      M = exp(clip(a_res mat(r) + b_res, clamp_min, clamp_max)), then
      `hc_sinkhorn_iters` times: every column divided by its sum (+ hc_eps),
      then every row by its sum (+ hc_eps): a Python loop of plain divisions
      u = sum_j H_pre[j] X[j];  y = F(RMSNorm_C(u))
      X'[i] = sum_j M[i, j] X[j] + H_post[i] y
  attention    c_q = RMSNorm(h W_qa); q = c_q W_qb -> heads of [q_nope | q_pe];
      [c | k_pe] = h W_kva; c = RMSNorm(c); [k_nope | v] = c W_kvb a head; RoPE
      on q_pe and on k_pe (one k_pe for all heads) with YaRN's frequencies
      inv_freq_i = theta^(-2i/d) (m_i + (1 - m_i) / factor), m_i = 1 -
      clip((i - low) / (high - low), 0, 1); scores (q . k) * s, s = (1 /
      sqrt(nope + rope)) (0.1 mscale_all_dim ln(factor) + 1)^2; causal softmax;
      probs . v; W_o. ALWAYS this decompressed form over the whole sequence.
  feed-forward layers < first_k_dense_replace a SwiGLU of `intermediate_size`;
      later layers `sum_{e in top-k} w_e E_e(h) + S(h)` — routing, an expert and
      the every-expert loop are `reference/latent_moe.py`'s, imported: the same
      mathematics letter for letter.

No cache, no absorption, no batching tricks, no kernels, nothing imported from
the program. Departures from the published code are the configuration file's
`assumed`. Weights are a nested dict as `reference/latent_moe.py`'s, with
`attention: {"wq_a", "q_norm", "wq_b", ...}` and `hc_attn` / `hc_ffn: {"phi"
[nC, n + n + n^2], "alpha" [3: pre, post, res], "b_pre" [n], "b_post" [n],
"b_res" [n, n]}` in float32. They stay in the type they are served in; the
forward pass upcasts one layer, and inside it one expert, at a time; the
embedding table stays on the host and the head is computed a block of the
vocabulary at a time, so that the check fits beside 10.4 GB of weights.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import math
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location(f"chipbench_reference_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_base = _sibling("latent_moe")  # routing, an expert, RMSNorm, SwiGLU: the same mathematics
rms_norm, swiglu, experts = _base.rms_norm, _base.swiglu, _base.experts
NEAR_TIE_MARGINS, TRIED_MARGINS = _base.NEAR_TIE_MARGINS, _base.TRIED_MARGINS

#: The head is computed this many vocabulary rows at a time.
HEAD_BLOCK = 16384


def map_count(n: int) -> int:
    return n + n + n * n


def param_counts(c: dict) -> dict:
    """Parameter counts by part, and of the whole model as `c` cuts it."""
    h, v, n = c["hidden_size"], c["vocab_size"], c["hc_mult"]
    heads, nope, rope = c["num_attention_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    rank, q_rank, vd, f = c["kv_lora_rank"], c["q_lora_rank"], c["v_head_dim"], c["moe_intermediate_size"]
    attention = (h * q_rank + q_rank + q_rank * heads * (nope + rope)  # W_qa, its norm, W_qb
                 + h * (rank + rope) + rank + rank * heads * (nope + vd)  # W_kva, the latent's norm, W_kvb
                 + heads * vd * h)
    maps = n * h * map_count(n) + 3 + map_count(n)  # Phi, the three alphas, the biases: one sub-layer's
    norms = 2 * h
    shared = 3 * h * c["n_shared_experts"] * f
    router = h * c["n_routed_experts"] + c["n_routed_experts"]
    expert = 3 * h * f
    outside = attention + 2 * maps + norms
    dense_layer = outside + 3 * h * c["intermediate_size"]
    expert_layer = outside + shared + router + c["n_routed_experts"] * expert
    n_dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
    n_expert = c["num_hidden_layers"] - n_dense
    layers = n_dense * dense_layer + n_expert * expert_layer
    return {
        "embedding": v * h, "head": h * v, "final_norm": h,
        "attention": attention, "maps": maps, "shared_expert": shared, "router": router, "routed_expert": expert,
        "outside_routed_experts": outside + shared + router,
        "dense_layer": dense_layer, "expert_layer": expert_layer,
        "dense_layers": n_dense, "expert_layers": n_expert, "layers": layers,
        "total": 2 * v * h + h + layers,
    }


def yarn_range(scaling: dict, dim: int, theta: float) -> tuple:
    """`(low, high)`: the rope pairs between which YaRN's ramp runs (DeepSeek-V3's
    `yarn_find_correction_range`)."""
    def pair_of(rotations):
        return dim * math.log(scaling["original_max_position_embeddings"] / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    return max(math.floor(pair_of(scaling["beta_fast"])), 0), min(math.ceil(pair_of(scaling["beta_slow"])), dim - 1)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 or not mscale else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(c: dict) -> float:
    """`s`: 1 / sqrt(192) times (0.1 ln 64 + 1)^2 = 2.0047 as published."""
    scaling = c["rope_scaling"]
    return yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2 / math.sqrt(
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"])


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
    q_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_norm_eps: float
    hc_mult: int
    hc_sinkhorn_iters: int
    hc_eps: float
    mhc_h_res_clamp_min: float
    mhc_h_res_clamp_max: float
    yarn: tuple  # (factor, low, high, rope mscale, softmax scale)

    @classmethod
    def of(cls, config: dict) -> "_Sizes":
        scaling = config["rope_scaling"]
        if scaling.get("type") != "yarn":
            raise ValueError("this reference applies YaRN: rope_scaling.type must be \"yarn\"")
        low, high = yarn_range(scaling, config["qk_rope_head_dim"], float(config["rope_theta"]))
        rope_mscale = (yarn_mscale(scaling["factor"], scaling["mscale"])
                       / yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]))
        flat = dict(config, yarn=(float(scaling["factor"]), low, high, rope_mscale, softmax_scale(config)))
        return cls(*(flat[f] for f in cls._fields))


# ------------------------------------------------------------------- the weights
def init_params(config: dict, key, dtype=jnp.bfloat16):
    """Every weight from `key`, as HOST arrays in the type they are served in
    (the maps, their alphas and biases and the router's choice bias float32).
    The configuration file's `init` gives the scales: a kernel's standard
    deviation is `gain[name] / sqrt(fan_in)`, the embedding's `embedding_std`,
    RMSNorm scales 1 + normal(0, `norm_scale_std`), `Phi` `hc.phi_gain /
    sqrt(n C)`, the alphas constants, `b_pre` and `b_post` normal(0,
    `hc.b_std`), `b_res` `hc.b_res_diag` on the diagonal + normal(0,
    `hc.b_std`). Made on the device one leaf a call and fetched at once, as
    `reference/latent_moe.init_params` does and for its reasons."""
    s = _Sizes.of(config)
    init = config["init"]
    gain, hc = init["gain"], init["hc"]
    dtype = jnp.dtype(dtype).name
    h, f, n = s.hidden_size, s.moe_intermediate_size, s.hc_mult
    heads, nope, rope, rank, q_rank, vd = (s.num_attention_heads, s.qk_nope_head_dim, s.qk_rope_head_dim,
                                           s.kv_lora_rank, s.q_lora_rank, s.v_head_dim)
    counter = iter(range(1 << 20))

    def normal(*shape, std, dtype=dtype, one_plus=False):
        return np.asarray(_base._normal(jax.random.fold_in(key, next(counter)), shape, float(std), dtype, one_plus))

    def kernel(name, *shape):
        return {"kernel": normal(*shape, std=gain[name] / math.sqrt(shape[-2]))}

    def norm(width):
        return {"scale": normal(width, std=init["norm_scale_std"], one_plus=True)}

    def swiglu_weights(width, down):
        return {"w_gate": kernel("w_gate", h, width), "w_up": kernel("w_up", h, width),
                "w_down": kernel(down, width, h)}

    def maps():
        return {
            "phi": normal(n * h, map_count(n), std=hc["phi_gain"] / math.sqrt(n * h), dtype="float32"),
            "alpha": np.asarray(hc["alpha"], np.float32),
            "b_pre": normal(n, std=hc["b_std"], dtype="float32"),
            "b_post": normal(n, std=hc["b_std"], dtype="float32"),
            "b_res": (normal(n, n, std=hc["b_std"], dtype="float32")
                      + np.float32(hc["b_res_diag"]) * np.eye(n, dtype=np.float32)),
        }

    params = {"embed_tokens": {"embedding": normal(s.vocab_size, h, std=init["embedding_std"])}}
    for i in range(s.num_hidden_layers):
        layer = {
            "input_norm": norm(h), "post_attn_norm": norm(h), "hc_attn": maps(), "hc_ffn": maps(),
            "attention": {"wq_a": kernel("wq_a", h, q_rank), "q_norm": norm(q_rank),
                          "wq_b": kernel("wq_b", q_rank, heads * (nope + rope)),
                          "wkv_a": kernel("wkv_a", h, rank + rope), "kv_norm": norm(rank),
                          "wkv_b": kernel("wkv_b", rank, heads * (nope + vd)), "wo": kernel("wo", heads * vd, h)},
        }
        if i < s.first_k_dense_replace:
            layer["mlp"] = swiglu_weights(s.intermediate_size, "w_down")
        else:
            e = s.n_routed_experts
            layer["moe"] = {
                "router": kernel("router", h, e),
                "router_bias": normal(e, std=init["router_bias_std"], dtype="float32"),
                "experts": {"w_gate": kernel("w_gate", e, h, f), "w_up": kernel("w_up", e, h, f),
                            "w_down": kernel("expert_w_down", e, f, h)},
                "shared": swiglu_weights(s.n_shared_experts * f, "expert_w_down"),
            }
        params[f"layer_{i}"] = layer
    params["final_norm"] = norm(h)
    params["lm_head"] = kernel("lm_head", h, s.vocab_size)
    return {"params": params}


# --------------------------------------------------------------- the forward pass
def rotary(x, positions, s: _Sizes):
    """x [b, t, heads, d]: every dim rotated, dimension i paired with i + d/2,
    at YaRN's frequencies; cos and sin times the rope mscale (1.0 as published)."""
    d = x.shape[-1]
    factor, low, high, rope_mscale, _ = s.yarn
    pairs = jnp.arange(d // 2, dtype=jnp.float32)
    keep = 1.0 - jnp.clip((pairs - low) / max(high - low, 0.001), 0.0, 1.0)
    inv_freq = s.rope_theta ** (-2.0 * pairs / d) * (keep + (1.0 - keep) / factor)
    angles = positions[:, :, None, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles) * rope_mscale, jnp.sin(angles) * rope_mscale
    x1, x2 = x[..., : d // 2].astype(jnp.float32), x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def attention(p, x, s: _Sizes):
    b, t, _ = x.shape
    heads, nope, rope, rank, vd = (s.num_attention_heads, s.qk_nope_head_dim, s.qk_rope_head_dim,
                                   s.kv_lora_rank, s.v_head_dim)
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    c_q = rms_norm(x @ p["wq_a"]["kernel"], p["q_norm"], s.rms_norm_eps)
    q = (c_q @ p["wq_b"]["kernel"]).reshape(b, t, heads, nope + rope)
    q_nope, q_pe = q[..., :nope], rotary(q[..., nope:], positions, s)
    row = x @ p["wkv_a"]["kernel"]
    c = rms_norm(row[..., :rank], p["kv_norm"], s.rms_norm_eps)
    k_pe = rotary(row[:, :, None, rank:], positions, s)  # [b, t, 1, rope]
    kv = (c @ p["wkv_b"]["kernel"]).reshape(b, t, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
              + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe[:, :, 0])).astype(jnp.float32) * s.yarn[4]
    causal = jnp.tril(jnp.ones((t, t), bool))
    weights = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(b, t, heads * vd)
    return out @ p["wo"]["kernel"]


def hc_maps(p, X, s: _Sizes):
    """X [b, t, n, C] -> (H_pre [b, t, n], H_post [b, t, n], H_res [b, t, n, n],
    the largest |logit| the clamp was given)."""
    b, t, n, c = X.shape
    flat = X.reshape(b, t, n * c)
    xt = flat * jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + s.hc_eps)
    z = xt @ p["phi"]
    h_pre = jax.nn.sigmoid(p["alpha"][0] * z[..., :n] + p["b_pre"])
    h_post = 2.0 * jax.nn.sigmoid(p["alpha"][1] * z[..., n:2 * n] + p["b_post"])
    res_logits = p["alpha"][2] * z[..., 2 * n:].reshape(b, t, n, n) + p["b_res"]
    m = jnp.exp(jnp.clip(res_logits, s.mhc_h_res_clamp_min, s.mhc_h_res_clamp_max))
    for _ in range(s.hc_sinkhorn_iters):
        m = m / (m.sum(axis=-2, keepdims=True) + s.hc_eps)  # every column by its sum
        m = m / (m.sum(axis=-1, keepdims=True) + s.hc_eps)  # then every row by its sum
    return h_pre, h_post, m, jnp.abs(res_logits).max()


def hyper_connected(p, X, s: _Sizes, sublayer):
    """One sub-layer around the streams. Returns (X', what the check's
    statistics read: the maps and the RMS of `H_post y` over the streams')."""
    h_pre, h_post, h_res, peak = hc_maps(p, X, s)
    u = jnp.einsum("btn,btnc->btc", h_pre, X)
    y, extra = sublayer(u)
    added = h_post[..., None] * y[:, :, None, :]
    ratio = jnp.sqrt(jnp.mean(added * added) / jnp.mean(X * X))
    return jnp.einsum("btij,btjc->btic", h_res, X) + added, extra, (h_pre, h_post, h_res, ratio, peak)


@functools.partial(jax.jit, static_argnums=(2, 3))
def block(p, X, s: _Sizes, stats: bool = False):
    """One layer, float32 at matmul precision "highest". X [b, t, n, C] ->
    (X, the router's margins [b * t] or None for a dense layer, and with
    `stats` what `hyper_connected` reads of both sub-layers)."""
    with jax.default_matmul_precision("highest"):
        b, t, _, h = X.shape
        small = _base._f32({k: v for k, v in p.items() if k != "moe"})

        def attend(u):
            return attention(small["attention"], rms_norm(u, small["input_norm"], s.rms_norm_eps), s), None

        def feed_forward(u):
            normed = rms_norm(u, small["post_attn_norm"], s.rms_norm_eps)
            if "moe" not in p:
                return swiglu(normed, small["mlp"]), None
            y, margin = experts(p["moe"], normed.reshape(b * t, h), s)
            return y.reshape(b, t, h), margin

        X, _, seen_attn = hyper_connected(small["hc_attn"], X, s, attend)
        X, margin, seen_ffn = hyper_connected(small["hc_ffn"], X, s, feed_forward)
        return X, margin, ((seen_attn, seen_ffn) if stats else None)


@functools.partial(jax.jit, static_argnums=(3,))
def head_gaps(final_norm, lm_head_blocks, x, s: _Sizes, tokens):
    """For each row of x [b, n, hidden]: how far the logit of `tokens` [b, n]
    lies below the best logit, the head a block of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, final_norm, s.rms_norm_eps)
        best = jnp.full(tokens.shape, -jnp.inf, jnp.float32)
        chosen = jnp.zeros(tokens.shape, jnp.float32)
        first = 0
        for kernel in lm_head_blocks:
            logits = x @ kernel.astype(jnp.float32)
            width = kernel.shape[1]
            local = jnp.clip(tokens - first, 0, width - 1)
            mine = jnp.take_along_axis(logits, local[..., None], axis=-1)[..., 0]
            chosen = jnp.where((tokens >= first) & (tokens < first + width), mine, chosen)
            best = jnp.maximum(best, logits.max(-1))
            first += width
        return best - chosen


def _head_blocks(lm_head) -> tuple:
    kernel = lm_head["kernel"]
    return tuple(kernel[:, i:i + HEAD_BLOCK] for i in range(0, kernel.shape[1], HEAD_BLOCK))


def hidden_states(params, config: dict, ids, margins: list | None = None, stats: list | None = None):
    """ids [b, t] (host) -> the streams' sum after the last layer [b, t, hidden],
    layer by layer so that only one layer's float32 copy is alive at a time.
    `margins` collects each expert layer's router margins [b, t]; `stats` each
    layer's maps and `H_post y` ratios (`block`)."""
    s = _Sizes.of(config)
    inner = params["params"]
    x = jnp.asarray(np.asarray(inner["embed_tokens"]["embedding"])[np.asarray(ids)]).astype(jnp.float32)
    X = jnp.repeat(x[:, :, None, :], s.hc_mult, axis=2)
    for i in range(s.num_hidden_layers):
        X, margin, seen = block(inner[f"layer_{i}"], X, s, stats is not None)
        if margins is not None and margin is not None:
            margins.append(margin.reshape(np.asarray(ids).shape))
        if stats is not None:
            stats.append(seen)
    return X.sum(axis=2)


def logits(params, config: dict, ids):
    """Full logits [b, t, vocab]; for tests at small sizes."""
    s = _Sizes.of(config)
    x = hidden_states(params, config, ids)
    return _base._logits(params["params"]["final_norm"], params["params"]["lm_head"], x, s)


def served_token_gaps(params, config: dict, served: list, pad_to: int, rows: int, batch: int = 1) -> list:
    """Teacher-forced check of served requests, as
    `reference/latent_moe.served_token_gaps` makes it and with its interface:
    each `(prompt_ids, generated_tokens)` runs once through the reference as
    prompt + generated[:-1], right-padded to `pad_to`, and a generated token is
    held against the reference's best logit at its position — only where EVERY
    expert layer's router margin at its position (the last chosen biased score
    less the first not chosen: fourth against fifth here, the reference's own
    float32 numbers) is at least the configuration file's `check.router_margin`.
    Returns one float array of gaps a request; prints the same two JSON lines."""
    s = _Sizes.of(config)
    least_margin = float(config.get("check", {}).get("router_margin", 0.0))
    host = params["params"]
    # once, after the program has gone; the embedding table stays on the host and the head goes over in blocks
    inner = dict(jax.device_put({k: v for k, v in host.items() if k not in ("embed_tokens", "lm_head")}),
                 embed_tokens=host["embed_tokens"])
    params = {"params": inner}
    head = jax.device_put(_head_blocks(host["lm_head"]))
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
        x = hidden_states(params, config, ids, margins)
        margins = jax.device_get(margins)
        for margin in margins:
            routed += int(real.sum())
            for m, limit in enumerate(NEAR_TIE_MARGINS):
                near[m] += int((margin[real] < limit).sum())
        least = np.min(np.stack(margins), axis=0) if margins else np.full(ids.shape, np.inf, np.float32)
        index = jnp.minimum(jnp.asarray(first)[:, None] + jnp.arange(rows)[None, :], pad_to - 1)
        x = jnp.take_along_axis(x, index[..., None], axis=1)
        gaps = np.asarray(jax.device_get(head_gaps(inner["final_norm"], head, x, s, jnp.asarray(tokens))))
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


def init_statistics(params, config: dict, ids) -> list:
    """What the configuration file's `init.measured` records, of the float32
    reference on `ids` [b, t]: for every sub-layer in order (a layer's
    attention, then its feed-forward) the least standard deviation over tokens
    of an entry of H_pre and of H_post, the mean distance of H_res from the
    identity and from 1/n, how far its rows and columns are from summing to 1,
    the largest |logit| the clamp was given, and the RMS of `H_post y` over
    the streams'."""
    s = _Sizes.of(config)
    stats: list = []
    hidden_states(params, config, ids, stats=stats)
    n = s.hc_mult
    out = []
    for layer in jax.device_get(stats):
        for h_pre, h_post, h_res, ratio, peak in layer:
            out.append({
                "h_pre_std_min": float(h_pre.reshape(-1, n).std(0).min()),
                "h_post_std_min": float(h_post.reshape(-1, n).std(0).min()),
                "h_res_from_identity": float(np.abs(h_res - np.eye(n)).mean()),
                "h_res_from_uniform": float(np.abs(h_res - 1.0 / n).mean()),
                "h_res_row_sum_err": float(np.abs(h_res.sum(-1) - 1).max()),
                "h_res_col_sum_err": float(np.abs(h_res.sum(-2) - 1).max()),
                "res_logit_abs_max": float(peak),
                "h_post_y_over_streams_rms": float(ratio),
            })
    return out
