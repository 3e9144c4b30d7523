"""Falcon-H1 (every block a Mamba-2 state-space mixer and grouped-query softmax
attention side by side on one normed input, under muP multipliers) in plain
`jax.numpy`: the seeded weights and the float32 forward pass that decides
`correct` for the cells that serve it.

With ``u = RMSNorm(h)``, RMSNorm eps from the config, no biases but the
convolution's, names as the published keys:

    h <- h + ssm_out_multiplier * Mixer(ssm_in_multiplier * u)
           + attention_out_multiplier * Attn(attention_in_multiplier * u)
    h <- h + MLP(RMSNorm(h))

  Mixer   [z | x | B | C | dt] = (u W_in) * m, m the five `ssm_multipliers`
      spread over the segments (widths d_ssm, d_ssm, groups * d_state twice,
      heads); [x | B | C] <- silu(conv([x | B | C]) + b), a causal depthwise
      convolution of `mamba_d_conv` taps (a plain sum over the taps, zeros
      before the first token); dt <- softplus(dt + dt_bias), A = -exp(A_log).
      With H [d_head, d_state] a head, H_0 = 0, heads 0 .. heads/groups - 1 on
      group 0's B and C, TOKEN BY TOKEN (`lax.scan` over t — never a chunked
      form: that is the program's, and this file is what it is held against):
          H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T
          y_t = H_t C_t + D x_t
      o = RMSNorm_group(y * silu(z)) over each group's d_ssm / groups channels
      with a d_ssm-wide scale (`mamba_norm_before_gate` false); out = o W_out.
  Attn    `num_attention_heads` query heads over `num_key_value_heads` heads of
      keys and values (repeated), all of `head_dim`; k <- k * key_multiplier;
      rotary embedding over the whole head, half-split pairs, `rope_theta`;
      causal softmax of q k^T / sqrt(head_dim); W_o.
  MLP     W_down(silu(W_gate(v) * mlp_multipliers[0]) * W_up(v)) * mlp_multipliers[1].

Embedding E[ids] * embedding_multiplier; final RMSNorm; logits = (h W_head) *
lm_head_multiplier, untied.

No cache, no batching tricks, no kernels, nothing imported from the program.
Every departure from the published code and every assumed size is a line of
the configuration file's `assumed`.

Weights are a nested dict, `{"params": {"embed_tokens": {"embedding"},
"layer_<i>": {"input_norm", "pre_mlp_norm": {"scale"}, "mixer": {"w_in", "w_out":
{"kernel"}, "conv_weight" [taps, channels], "conv_bias" [channels], "A_log",
"dt_bias", "D" [heads] float32, "norm_scale" [d_ssm]}, "attention": {"wq", "wk",
"wv", "wo": {"kernel"}}, "mlp": {"w_gate", "w_up", "w_down": {"kernel"}}},
"final_norm", "lm_head": {"kernel"}}}`, kernels `[in, out]`. They stay in the
type they are served in; the forward pass upcasts one layer at a time, and the
head a block of the vocabulary at a time.

The seeded initialisation (`init_params`). With `normal(0, 0.02)` kernels the
published multipliers leave the residual stream almost the embedding alone and
a wrong mixer would pass any check of served tokens. So every tensor's scale
undoes the multipliers around it: `std = gain / sqrt(fan_in) / (the multipliers
applied to its input and to its output)`, `gain` by tensor from the
configuration file's `init.gain` — what the tensor's output reads for an input
of RMS 1, at any width. The file's `assumed` gives each branch's measured share
of the stream.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

#: Most columns of the head computed at a time (a float32 copy of the block and
#: the rows' logits over it are alive, not the whole head's): 8 blocks of 32,640
#: at the published 261,120.
VOCAB_BLOCK = 32768

#: `init.gain` where the configuration file names none.
DEFAULT_GAIN = {"embedding": 1.0, "wq": 1.4, "wk": 1.4, "wv": 1.4, "wo": 0.7, "w_in": 1.4, "conv": 1.0,
                "conv_bias": 0.1, "w_out": 0.5, "w_gate": 1.4, "w_up": 1.4, "w_down": 0.5, "lm_head": 1.4,
                "norm_scale": 0.02}


def param_counts(c: dict) -> dict:
    """Parameter counts by part, and of the whole model as `c` cuts it."""
    h, v, f = c["hidden_size"], c["vocab_size"], c["intermediate_size"]
    d_ssm, heads = c["mamba_d_ssm"], c["mamba_n_heads"]
    bc = c["mamba_n_groups"] * c["mamba_d_state"]
    channels = d_ssm + 2 * bc
    w_in = h * (2 * d_ssm + 2 * bc + heads)
    mixer = (w_in + (c["mamba_d_conv"] + 1) * channels  # the taps and the bias
             + 3 * heads  # dt_bias, A_log, D
             + d_ssm  # the gated norm's scale
             + d_ssm * h)  # W_out
    q, kv = c["num_attention_heads"] * c["head_dim"], c["num_key_value_heads"] * c["head_dim"]
    attention = h * q + 2 * h * kv + q * h
    mlp = 3 * h * f
    norms = 2 * h  # a block's two RMSNorms
    layer = mixer + attention + mlp + norms
    layers = c["num_hidden_layers"] * layer
    return {
        "embedding": v * h, "head": h * v, "final_norm": h, "w_in": w_in,
        "mixer": mixer, "attention": attention, "mlp": mlp, "layer": layer, "layers": layers,
        "total": 2 * v * h + h + layers,
    }


class _Sizes(NamedTuple):
    """The numbers `init_params` and the forward pass need, hashable for jit."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    rms_norm_eps: float
    mamba_d_ssm: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_n_groups: int
    mamba_d_state: int
    mamba_d_conv: int
    embedding_multiplier: float
    lm_head_multiplier: float
    attention_in_multiplier: float
    attention_out_multiplier: float
    key_multiplier: float
    ssm_in_multiplier: float
    ssm_out_multiplier: float
    ssm_multipliers: tuple
    mlp_multipliers: tuple

    @classmethod
    def of(cls, config: dict) -> "_Sizes":
        flat = dict(config, ssm_multipliers=tuple(config["ssm_multipliers"]),
                    mlp_multipliers=tuple(config["mlp_multipliers"]),
                    rope_theta=float(config["rope_theta"]))  # 1e11 as published: an integer no int32 holds
        if flat["mamba_n_heads"] * flat["mamba_d_head"] != flat["mamba_d_ssm"]:
            raise ValueError("mamba_d_ssm is not mamba_n_heads x mamba_d_head")
        return cls(*(flat[f] for f in cls._fields))

    @property
    def segments(self) -> tuple:
        """Widths of W_in's output, in order: [z | x | B | C | dt]."""
        bc = self.mamba_n_groups * self.mamba_d_state
        return (self.mamba_d_ssm, self.mamba_d_ssm, bc, bc, self.mamba_n_heads)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _normal(key, shape: tuple, std: float, dtype: str, one_plus: bool = False):
    x = jax.random.normal(key, shape, jnp.float32) * std
    return (1.0 + x if one_plus else x).astype(jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnums=(1,))
def _decay_init(key, heads: int):
    """(A_log, dt_bias) float32: A = U(1, 16); dt = exp(U(log 0.001, log 0.1))
    and dt_bias its inverse softplus — Mamba-2's initialisation, so that the
    decay exp(dt A) spans slow and fast heads."""
    ka, kd = jax.random.split(key)
    a_log = jnp.log(jax.random.uniform(ka, (heads,), jnp.float32, 1.0, 16.0))
    dt = jnp.exp(jax.random.uniform(kd, (heads,), jnp.float32, math.log(0.001), math.log(0.1)))
    return a_log, dt + jnp.log(-jnp.expm1(-dt))


def init_stds(config: dict) -> dict:
    """The standard deviation every seeded tensor is drawn with: `gain /
    sqrt(fan_in) / (the multipliers around it)`; `w_in` one number a segment."""
    s = _Sizes.of(config)
    gain = dict(DEFAULT_GAIN, **config.get("init", {}).get("gain", {}))
    h = s.hidden_size
    q = s.num_attention_heads * s.head_dim
    fan = lambda name, fan_in, *multipliers: gain[name] / math.sqrt(fan_in) / math.prod(multipliers)  # noqa: E731
    return {
        "embedding": fan("embedding", 1, s.embedding_multiplier),
        "wq": fan("wq", h, s.attention_in_multiplier),
        "wk": fan("wk", h, s.attention_in_multiplier, s.key_multiplier),
        "wv": fan("wv", h, s.attention_in_multiplier),
        "wo": fan("wo", q, s.attention_out_multiplier),
        "w_in": tuple(fan("w_in", h, s.ssm_in_multiplier, m) for m in s.ssm_multipliers),
        "conv": fan("conv", s.mamba_d_conv),
        "conv_bias": gain["conv_bias"],
        "w_out": fan("w_out", s.mamba_d_ssm, s.ssm_out_multiplier),
        "w_gate": fan("w_gate", h, s.mlp_multipliers[0]),
        "w_up": fan("w_up", h),
        "w_down": fan("w_down", s.intermediate_size, s.mlp_multipliers[1]),
        "lm_head": fan("lm_head", h, s.lm_head_multiplier),
        "norm_scale": gain["norm_scale"],
    }


def init_params(config: dict, key, dtype=jnp.bfloat16):
    """Every weight from `key`, in the type it is served in, as HOST arrays:
    normal(0, `init_stds`) kernels, embeddings, convolution taps and bias, norm
    scales 1 + normal, `A_log` and `dt_bias` as `_decay_init` and `D` = 1 in
    float32 (the configuration file's `assumed`). Made on the device one leaf
    a call and fetched at once: the caller keeps this copy for the check while
    the program holds its own on the device, as a server that loaded a
    checkpoint does. `served_token_gaps` places them again once the program is
    gone."""
    s = _Sizes.of(config)
    std = init_stds(config)
    dtype = jnp.dtype(dtype).name
    h, f, d_ssm = s.hidden_size, s.intermediate_size, s.mamba_d_ssm
    q, kv = s.num_attention_heads * s.head_dim, s.num_key_value_heads * s.head_dim
    channels = d_ssm + 2 * s.mamba_n_groups * s.mamba_d_state
    counter = iter(range(1 << 20))

    def fold():
        return jax.random.fold_in(key, next(counter))

    def normal(name, *shape, one_plus=False):
        return np.asarray(_normal(fold(), shape, float(std[name]), dtype, one_plus))

    def kernel(name, *shape):
        return {"kernel": normal(name, *shape)}

    def norm(n):
        return {"scale": normal("norm_scale", n, one_plus=True)}

    params = {"embed_tokens": {"embedding": normal("embedding", s.vocab_size, h)}}
    for i in range(s.num_hidden_layers):
        a_log, dt_bias = (np.asarray(x) for x in _decay_init(fold(), s.mamba_n_heads))
        w_in = np.concatenate([np.asarray(_normal(fold(), (h, width), float(seg_std), dtype))
                               for width, seg_std in zip(s.segments, std["w_in"])], axis=1)
        params[f"layer_{i}"] = {
            "input_norm": norm(h), "pre_mlp_norm": norm(h),
            "mixer": {"w_in": {"kernel": w_in}, "conv_weight": normal("conv", s.mamba_d_conv, channels),
                      "conv_bias": normal("conv_bias", channels), "A_log": a_log, "dt_bias": dt_bias,
                      "D": np.ones((s.mamba_n_heads,), np.float32),
                      "norm_scale": normal("norm_scale", d_ssm, one_plus=True),
                      "w_out": kernel("w_out", d_ssm, h)},
            "attention": {"wq": kernel("wq", h, q), "wk": kernel("wk", h, kv), "wv": kernel("wv", h, kv),
                          "wo": kernel("wo", q, h)},
            "mlp": {"w_gate": kernel("w_gate", h, f), "w_up": kernel("w_up", h, f), "w_down": kernel("w_down", f, h)},
        }
    params["final_norm"] = norm(h)
    params["lm_head"] = kernel("lm_head", h, s.vocab_size)
    return {"params": params}


# --------------------------------------------------------------- the forward pass
def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def causal_conv(x, taps, bias):
    """x [b, t, channels], taps [W, channels]: y_t = sum_j taps[j] x_{t-(W-1)+j}
    + bias, zeros before the first token."""
    width, t = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * taps[j] for j in range(width)) + bias


def ssm_recurrence(x, dt, a, b_in, c_in):
    """The state-space recurrence, one token at a time. x [b, t, H, P], dt
    [b, t, H], a [H], b_in, c_in [b, t, H, N] (a group's repeated over its
    heads) -> y [b, t, H, P], without the skip."""
    b, _t, heads, p = x.shape
    n = b_in.shape[-1]

    def one_token(state, token):
        x_t, dt_t, b_t, c_t = token
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    tokens = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b_in, c_in))
    _, y = jax.lax.scan(one_token, jnp.zeros((b, heads, p, n), jnp.float32), tokens)
    return jnp.moveaxis(y, 0, 1)


def mixer(p, u, s: _Sizes):
    b, t, _ = u.shape
    heads, d_head, groups, n, d_ssm = s.mamba_n_heads, s.mamba_d_head, s.mamba_n_groups, s.mamba_d_state, s.mamba_d_ssm
    scale = jnp.concatenate([jnp.full((width,), m, jnp.float32) for width, m in zip(s.segments, s.ssm_multipliers)])
    projected = (u @ p["w_in"]["kernel"]) * scale
    z, xbc, dt = jnp.split(projected, [d_ssm, 2 * d_ssm + 2 * groups * n], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_weight"], p["conv_bias"]))
    x, b_in, c_in = jnp.split(xbc, [d_ssm, d_ssm + groups * n], axis=-1)
    x = x.reshape(b, t, heads, d_head)
    by_head = lambda v: jnp.repeat(v.reshape(b, t, groups, n), heads // groups, axis=2)  # noqa: E731
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssm_recurrence(x, dt, -jnp.exp(p["A_log"]), by_head(b_in), by_head(c_in)) + p["D"][:, None] * x
    gated = (y.reshape(b, t, d_ssm) * jax.nn.silu(z)).reshape(b, t, groups, d_ssm // groups)
    normed = gated * jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + s.rms_norm_eps)
    return (normed.reshape(b, t, d_ssm) * p["norm_scale"]) @ p["w_out"]["kernel"]


def rotary(x, theta: float):
    """x [b, t, heads, d], positions 0 .. t-1: half-split pairs over the whole head."""
    d, t = x.shape[-1], x.shape[1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq  # [t, d / 2]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(p, u, s: _Sizes):
    b, t, _ = u.shape
    hq, hkv, d = s.num_attention_heads, s.num_key_value_heads, s.head_dim
    q = rotary((u @ p["wq"]["kernel"]).reshape(b, t, hq, d), s.rope_theta)
    k = rotary(((u @ p["wk"]["kernel"]) * s.key_multiplier).reshape(b, t, hkv, d), s.rope_theta)
    v = (u @ p["wv"]["kernel"]).reshape(b, t, hkv, d)
    k, v = (jnp.repeat(x, hq // hkv, axis=2) for x in (k, v))  # a KV head serves hq / hkv query heads in a row
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((t, t), bool))
    weights = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(b, t, hq * d) @ p["wo"]["kernel"]


def mlp(p, v, s: _Sizes):
    gate = jax.nn.silu((v @ p["w_gate"]["kernel"]) * s.mlp_multipliers[0])
    return ((gate * (v @ p["w_up"]["kernel"])) @ p["w_down"]["kernel"]) * s.mlp_multipliers[1]


def branches(p, x, s: _Sizes):
    """One layer's three branches as they are ADDED to the stream, multipliers
    applied: (mixer, attention, mlp), the last computed on the stream after
    the first two. float32 at matmul precision "highest"."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        u = rms_norm(x, p["input_norm"]["scale"], s.rms_norm_eps)
        mixed = s.ssm_out_multiplier * mixer(p["mixer"], s.ssm_in_multiplier * u, s)
        attended = s.attention_out_multiplier * attention(p["attention"], s.attention_in_multiplier * u, s)
        x = x + mixed + attended
        return mixed, attended, mlp(p["mlp"], rms_norm(x, p["pre_mlp_norm"]["scale"], s.rms_norm_eps), s)


@functools.partial(jax.jit, static_argnums=(2,))
def block(p, x, s: _Sizes):
    """One layer. x [b, t, hidden] -> x."""
    mixed, attended, ffn = branches(p, x, s)
    return x + mixed + attended + ffn


def _embed(params, ids, s: _Sizes):
    """Rows of the table gathered where the table lives (the host, for host arrays)."""
    table = params["params"]["embed_tokens"]["embedding"]
    return jnp.asarray(table[np.asarray(ids)] if isinstance(table, np.ndarray) else table[ids]
                       ).astype(jnp.float32) * s.embedding_multiplier


def _head_block(vocab: int) -> int:
    """Columns a block: the vocabulary in the fewest equal blocks of at most `VOCAB_BLOCK`."""
    blocks = -(-vocab // VOCAB_BLOCK)
    while vocab % blocks:
        blocks += 1
    return vocab // blocks


@functools.partial(jax.jit, static_argnums=(3,))
def head_gaps(final_norm, lm_head, x, s: _Sizes, tokens):
    """For each row of x [b, n, hidden]: how far the logit of `tokens` [b, n]
    lies below the best logit, the head computed a block of the vocabulary at
    a time. 0 where the token is the reference's own choice."""
    kernel = lm_head["kernel"]
    width = _head_block(s.vocab_size)
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, final_norm["scale"].astype(jnp.float32), s.rms_norm_eps)

        def one_block(carry, start):
            best, chosen = carry
            columns = jax.lax.dynamic_slice_in_dim(kernel, start, width, axis=1).astype(jnp.float32)
            logits = (x @ columns) * s.lm_head_multiplier
            local = tokens - start
            inside = (local >= 0) & (local < width)
            mine = jnp.take_along_axis(logits, jnp.clip(local, 0, width - 1)[..., None], axis=-1)[..., 0]
            return (jnp.maximum(best, logits.max(-1)), jnp.where(inside, mine, chosen)), None

        start = (jnp.full(tokens.shape, -jnp.inf, jnp.float32), jnp.zeros(tokens.shape, jnp.float32))
        (best, chosen), _ = jax.lax.scan(one_block, start, jnp.arange(0, s.vocab_size, width))
    return best - chosen


def hidden_states(params, config: dict, ids, shares: list | None = None):
    """ids [b, t] -> the last layer's output [b, t, hidden], layer by layer so
    that only one layer's float32 copy is alive at a time. `shares` collects,
    a layer, the RMS of each branch's output over the RMS of the stream it is
    added to: (mixer, attention, mlp)."""
    s = _Sizes.of(config)
    inner = params["params"]
    x = _embed(params, ids, s)
    rms = lambda v: float(jnp.sqrt(jnp.mean(v * v)))  # noqa: E731
    for i in range(s.num_hidden_layers):
        if shares is not None:
            mixed, attended, ffn = jax.jit(branches, static_argnums=(2,))(inner[f"layer_{i}"], x, s)
            shares.append((rms(mixed) / rms(x), rms(attended) / rms(x), rms(ffn) / rms(x + mixed + attended)))
        x = block(inner[f"layer_{i}"], x, s)
    return x


def logits(params, config: dict, ids):
    """Full logits [b, t, vocab]; for tests at small sizes."""
    s = _Sizes.of(config)
    x = hidden_states(params, config, ids)
    inner = params["params"]
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, jnp.asarray(inner["final_norm"]["scale"], jnp.float32), s.rms_norm_eps)
        return (x @ jnp.asarray(inner["lm_head"]["kernel"], jnp.float32)) * s.lm_head_multiplier


def served_token_gaps(params, config: dict, served: list, pad_to: int, rows: int, batch: int = 2) -> list:
    """Teacher-forced check of served requests. `served` is a list of
    `(prompt_ids, generated_tokens)`; each is run once through the reference as
    prompt + generated[:-1], right-padded to `pad_to` (causal in both halves of
    a layer: a pad is never seen by a real position), the recurrence carried
    over every position, and a generated token is held against the
    reference's best logit at its position. Returns one float array of gaps a
    request. `rows` bounds the generated tokens of one request (the head is
    computed on that many positions). The embedding table stays on the host
    (its rows are gathered there); the layers and the head are placed once."""
    s = _Sizes.of(config)
    inner = dict(params["params"])
    table = inner.pop("embed_tokens")
    inner = jax.device_put(inner)  # once, after the program has gone: they come as host arrays
    params = {"params": dict(inner, embed_tokens=table)}
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
        x = hidden_states(params, config, ids)
        index = jnp.minimum(jnp.asarray(first)[:, None] + jnp.arange(rows)[None, :], pad_to - 1)
        x = jnp.take_along_axis(x, index[..., None], axis=1)
        gaps = np.asarray(jax.device_get(
            head_gaps(inner["final_norm"], inner["lm_head"], x, s, jnp.asarray(tokens))))
        for j, (_prompt, generated) in enumerate(group):
            out.append(gaps[j, : len(generated)])
    return out
