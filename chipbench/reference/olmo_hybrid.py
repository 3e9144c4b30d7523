"""Olmo-Hybrid-7B (gated-delta-rule linear-attention layers, a full softmax-
attention layer every fourth) in plain `jax.numpy`: the seeded weights and the
float32 forward pass that decides `correct` for the cells that serve it.

Per layer (`layer_types` says which kind), RMSNorm eps from the config, no
biases, the family's reordered norm:

    h = x + RMSNorm(mixer(x));  y = h + RMSNorm(SwiGLU(h))

  linear_attention   [q~ | k~ | v~] = x [W_q | W_k | W_v]; [q | k | v] = silu of a
      causal depthwise convolution of `linear_conv_kernel_dim` taps over those
      channels (a plain sum over the taps, zeros before the first token); by
      head q <- q / |q| / sqrt(key_dim), k <- k / |k|; beta = sigmoid(x W_b),
      doubled when `linear_allow_neg_eigval`; alpha = exp(-exp(A_log) softplus(x W_a
      + dt_bias)). With S [key_dim, value_dim] a head, S_0 = 0, TOKEN BY TOKEN
      (`lax.scan` over t — never a chunked form: that is the program's, and
      this file is what it is held against):
          S_t = alpha_t S_{t-1} + beta_t k_t (v_t - (alpha_t S_{t-1})^T k_t)^T
          o_t = S_t^T q_t
      out = [RMSNorm_head(o_t) * silu(x W_g)] W_o.
  full_attention     heads of hidden / heads; q = RMSNorm(x W_q), k = RMSNorm(x W_k)
      over the whole projection; causal softmax of q k^T / sqrt(head_dim); no
      rotary embedding (`rope_parameters.rope_theta` is null); W_o.

No cache, no batching tricks, no kernels, nothing imported from the program.
Every departure from the published code and every assumed size is a line of
the configuration file's `assumed`.

Weights are a nested dict, `{"params": {"embed_tokens": {"embedding"},
"layer_<i>": {"mixer": {"wq", "wk", "wv", "wg", "wo", "wa", "wb": {"kernel"},
"conv_weight" [taps, channels], "A_log", "dt_bias" [heads] float32, "out_norm":
{"scale"}} | {"wq", "wk", "wv", "wo": {"kernel"}, "q_norm", "k_norm": {"scale"}},
"post_mixer_norm", "post_mlp_norm": {"scale"}, "mlp": {"w_gate", "w_up",
"w_down": {"kernel"}}}, "final_norm", "lm_head": {"kernel"}}}`, kernels `[in,
out]`. They stay in the type they are served in; the forward pass upcasts one
layer at a time.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

LINEAR, FULL = "linear_attention", "full_attention"
#: `q / sqrt(|q|^2 + L2_EPS)`: the delta rule's reference kernels normalise so.
L2_EPS = 1e-6


def param_counts(c: dict) -> dict:
    """Parameter counts by part, and of the whole model as `c` cuts it."""
    h, v, f = c["hidden_size"], c["vocab_size"], c["intermediate_size"]
    heads, dk, dv = c["linear_num_value_heads"], c["linear_key_head_dim"], c["linear_value_head_dim"]
    channels = heads * (2 * dk + dv)
    linear_mixer = (h * channels  # W_q, W_k, W_v
                    + 2 * h * heads * dv  # W_g, W_o
                    + 2 * h * heads  # W_a, W_b
                    + c["linear_conv_kernel_dim"] * channels
                    + 2 * heads  # A_log, dt_bias
                    + dv)  # the output norm, one scale for every head
    full_mixer = 4 * h * h + 2 * h  # four projections and the QK-norm's two scales
    mlp = 3 * h * f
    norms = 2 * h  # a block's two RMSNorms
    linear_layer, full_layer = linear_mixer + mlp + norms, full_mixer + mlp + norms
    kinds = c["layer_types"]
    n_linear, n_full = kinds.count(LINEAR), kinds.count(FULL)
    layers = n_linear * linear_layer + n_full * full_layer
    return {
        "embedding": v * h, "head": h * v, "final_norm": h,
        "linear_mixer": linear_mixer, "full_mixer": full_mixer, "mlp": mlp,
        "linear_layer": linear_layer, "full_layer": full_layer,
        "linear_layers": n_linear, "full_layers": n_full, "layers": layers,
        "total": 2 * v * h + h + layers,
    }


class _Sizes(NamedTuple):
    """The numbers `init_params` and the forward pass need, hashable for jit."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    linear_allow_neg_eigval: bool
    rms_norm_eps: float
    layer_types: tuple
    init_std: float

    @classmethod
    def of(cls, config: dict) -> "_Sizes":
        flat = dict(config, init_std=config.get("init", {}).get("std", 0.02),
                    layer_types=tuple(config["layer_types"]))
        if len(flat["layer_types"]) != config["num_hidden_layers"]:
            raise ValueError("layer_types does not name num_hidden_layers layers")
        return cls(*(flat[f] for f in cls._fields))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _normal(key, shape: tuple, std: float, dtype: str, one_plus: bool = False):
    x = jax.random.normal(key, shape, jnp.float32) * std
    return (1.0 + x if one_plus else x).astype(jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnums=(1,))
def _decay_init(key, heads: int):
    """(A_log, dt_bias) float32: A = U(1, 16); dt = exp(U(log 0.001, log 0.1))
    and dt_bias its inverse softplus — the delta rule paper's initialisation,
    so that alpha spans slow and fast heads."""
    ka, kd = jax.random.split(key)
    a_log = jnp.log(jax.random.uniform(ka, (heads,), jnp.float32, 1.0, 16.0))
    dt = jnp.exp(jax.random.uniform(kd, (heads,), jnp.float32, math.log(0.001), math.log(0.1)))
    return a_log, dt + jnp.log(-jnp.expm1(-dt))


def init_params(config: dict, key, dtype=jnp.bfloat16):
    """Every weight from `key`, in the type it is served in, as HOST arrays:
    normal(0, `init.std`) kernels, embeddings and convolution taps, RMSNorm
    scales 1 + normal, `A_log` and `dt_bias` as `_decay_init` in float32 (the
    configuration file's `assumed`). Made on the device one leaf a call and
    fetched at once: the caller keeps this copy for the check while the program
    holds its own on the device, as a server that loaded a checkpoint does.
    `served_token_gaps` places them again once the program is gone."""
    s = _Sizes.of(config)
    dtype = jnp.dtype(dtype).name
    h, f = s.hidden_size, s.intermediate_size
    heads, dk, dv = s.linear_num_value_heads, s.linear_key_head_dim, s.linear_value_head_dim
    counter = iter(range(1 << 20))

    def fold():
        return jax.random.fold_in(key, next(counter))

    def normal(*shape, one_plus=False):
        return np.asarray(_normal(fold(), shape, s.init_std, dtype, one_plus))

    def kernel(*shape):
        return {"kernel": normal(*shape)}

    def norm(n):
        return {"scale": normal(n, one_plus=True)}

    params = {"embed_tokens": {"embedding": normal(s.vocab_size, h)}}
    for i, kind in enumerate(s.layer_types):
        if kind == LINEAR:
            a_log, dt_bias = (np.asarray(x) for x in _decay_init(fold(), heads))
            mixer = {"wq": kernel(h, heads * dk), "wk": kernel(h, heads * dk), "wv": kernel(h, heads * dv),
                     "conv_weight": normal(s.linear_conv_kernel_dim, heads * (2 * dk + dv)),
                     "wb": kernel(h, heads), "wa": kernel(h, heads), "A_log": a_log, "dt_bias": dt_bias,
                     "out_norm": norm(dv), "wg": kernel(h, heads * dv), "wo": kernel(heads * dv, h)}
        else:
            mixer = {"wq": kernel(h, h), "wk": kernel(h, h), "wv": kernel(h, h), "wo": kernel(h, h),
                     "q_norm": norm(h), "k_norm": norm(h)}
        params[f"layer_{i}"] = {
            "mixer": mixer, "post_mixer_norm": norm(h), "post_mlp_norm": norm(h),
            "mlp": {"w_gate": kernel(h, f), "w_up": kernel(h, f), "w_down": kernel(f, h)},
        }
    params["final_norm"] = norm(h)
    params["lm_head"] = kernel(h, s.vocab_size)
    return {"params": params}


# --------------------------------------------------------------- the forward pass
def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * p["scale"]


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def swiglu(x, p):
    return (jax.nn.silu(x @ p["w_gate"]["kernel"]) * (x @ p["w_up"]["kernel"])) @ p["w_down"]["kernel"]


def causal_conv(x, taps):
    """x [b, t, channels], taps [W, channels]: y_t = sum_j taps[j] x_{t-(W-1)+j},
    zeros before the first token."""
    width, t = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * taps[j] for j in range(width))


def delta_recurrence(q, k, v, alpha, beta):
    """The gated delta rule, one token at a time. q, k [b, t, H, dk], v
    [b, t, H, dv], alpha, beta [b, t, H] -> o [b, t, H, dv]."""
    b, _t, heads, dk = q.shape
    dv = v.shape[-1]

    def one_token(state, token):
        q_t, k_t, v_t, a_t, b_t = token
        state = a_t[..., None, None] * state
        read = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + k_t[..., :, None] * (b_t[..., None] * (v_t - read))[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    tokens = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, alpha, beta))
    _, o = jax.lax.scan(one_token, jnp.zeros((b, heads, dk, dv), jnp.float32), tokens)
    return jnp.moveaxis(o, 0, 1)


def linear_attention(p, x, s: _Sizes):
    b, t, _ = x.shape
    heads, dk, dv = s.linear_num_value_heads, s.linear_key_head_dim, s.linear_value_head_dim
    qkv = jnp.concatenate([x @ p["wq"]["kernel"], x @ p["wk"]["kernel"], x @ p["wv"]["kernel"]], axis=-1)
    qkv = jax.nn.silu(causal_conv(qkv, p["conv_weight"]))
    q, k, v = jnp.split(qkv, [heads * dk, 2 * heads * dk], axis=-1)
    q = l2_norm(q.reshape(b, t, heads, dk)) / math.sqrt(dk)
    k = l2_norm(k.reshape(b, t, heads, dk))
    v = v.reshape(b, t, heads, dv)
    beta = jax.nn.sigmoid(x @ p["wb"]["kernel"]) * (2.0 if s.linear_allow_neg_eigval else 1.0)
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(x @ p["wa"]["kernel"] + p["dt_bias"]))
    o = delta_recurrence(q, k, v, alpha, beta)
    o = rms_norm(o, p["out_norm"], s.rms_norm_eps).reshape(b, t, heads * dv)
    return (o * jax.nn.silu(x @ p["wg"]["kernel"])) @ p["wo"]["kernel"]


def full_attention(p, x, s: _Sizes):
    b, t, h = x.shape
    heads = s.num_attention_heads
    d = h // heads
    q = rms_norm(x @ p["wq"]["kernel"], p["q_norm"], s.rms_norm_eps).reshape(b, t, heads, d)
    k = rms_norm(x @ p["wk"]["kernel"], p["k_norm"], s.rms_norm_eps).reshape(b, t, heads, d)
    v = (x @ p["wv"]["kernel"]).reshape(b, t, heads, d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((t, t), bool))
    weights = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(b, t, h) @ p["wo"]["kernel"]


@functools.partial(jax.jit, static_argnums=(2, 3))
def block(p, x, kind: str, s: _Sizes):
    """One layer, float32 at matmul precision "highest". x [b, t, hidden] -> x."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        mixer = linear_attention if kind == LINEAR else full_attention
        x = x + rms_norm(mixer(p["mixer"], x, s), p["post_mixer_norm"], s.rms_norm_eps)
        return x + rms_norm(swiglu(x, p["mlp"]), p["post_mlp_norm"], s.rms_norm_eps)


def _logits(final_norm, lm_head, x, s: _Sizes):
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, _f32(final_norm), s.rms_norm_eps)
        return x @ lm_head["kernel"].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(3,))
def head_gaps(final_norm, lm_head, x, s: _Sizes, tokens):
    """For each row of x [b, n, hidden]: how far the logit of `tokens` [b, n]
    lies below the best logit. 0 where the token is the reference's own choice."""
    logits = _logits(final_norm, lm_head, x, s)
    chosen = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    return logits.max(-1) - chosen


def hidden_states(params, config: dict, ids):
    """ids [b, t] -> the last layer's output [b, t, hidden], layer by layer so
    that only one layer's float32 copy is alive at a time."""
    s = _Sizes.of(config)
    inner = params["params"]
    x = jnp.asarray(inner["embed_tokens"]["embedding"])[ids].astype(jnp.float32)
    for i, kind in enumerate(s.layer_types):
        x = block(inner[f"layer_{i}"], x, kind, s)
    return x


def logits(params, config: dict, ids):
    """Full logits [b, t, vocab]; for tests at small sizes."""
    s = _Sizes.of(config)
    x = hidden_states(params, config, ids)
    return _logits(params["params"]["final_norm"], params["params"]["lm_head"], x, s)


def served_token_gaps(params, config: dict, served: list, pad_to: int, rows: int, batch: int = 2) -> list:
    """Teacher-forced check of served requests. `served` is a list of
    `(prompt_ids, generated_tokens)`; each is run once through the reference as
    prompt + generated[:-1], right-padded to `pad_to` (causal in both kinds of
    layer: a pad is never seen by a real position), the recurrence carried
    over every position, and a generated token is held against the
    reference's best logit at its position. Returns one float array of gaps a
    request. `rows` bounds the generated tokens of one request (the head is
    computed on that many positions)."""
    s = _Sizes.of(config)
    inner = jax.device_put(params["params"])  # once, after the program has gone: they come as host arrays
    params = {"params": inner}
    out = []
    for start in range(0, len(served), batch):
        group = served[start:start + batch]
        ids = np.zeros((len(group), pad_to), np.int32)
        tokens = np.zeros((len(group), rows), np.int32)
        first = np.zeros((len(group),), np.int32)
        for j, (prompt, generated) in enumerate(group):
            n = len(generated)
            if n > rows or len(prompt) + n - 1 > pad_to:
                raise ValueError("a served request is longer than the reference was sized for")
            ids[j, : len(prompt)] = prompt
            ids[j, len(prompt): len(prompt) + n - 1] = generated[:-1]
            tokens[j, :n] = generated
            first[j] = len(prompt) - 1
        x = hidden_states(params, config, jnp.asarray(ids))
        index = jnp.minimum(jnp.asarray(first)[:, None] + jnp.arange(rows)[None, :], pad_to - 1)
        x = jnp.take_along_axis(x, index[..., None], axis=1)
        gaps = np.asarray(jax.device_get(
            head_gaps(inner["final_norm"], inner["lm_head"], x, s, jnp.asarray(tokens))))
        for j, (_prompt, generated) in enumerate(group):
            out.append(gaps[j, : len(generated)])
    return out
