"""GPT-NeoX (Pythia) in plain `jax.numpy`: the seeded weights and the float32
forward pass that decides `correct` for the cells that serve this family.

Follows `GPTNeoXForCausalLM` as EleutherAI published it: token embedding; per
layer `x + attn(ln1(x)) + mlp(ln2(x))` (parallel residual), biased q/k/v/out and
MLP projections, rotary embedding on the first `rotary_pct` of each head in the
half-split (`rotate_half`) form, exact (erf) GELU; final LayerNorm; un-tied,
un-biased output head. No cache, no batching tricks, no kernels, and nothing
imported from the program. Departure: q, k and v are three matrices, not
HuggingFace's one interleaved `query_key_value` — the same mathematics, and the
layout the seeded weights are made in.

Weights are a nested dict, `{"params": {"embed_in": {"embedding"}, "layer_<i>":
{"input_norm", "post_attn_norm": {"scale", "bias"}, "attention": {"wq", "wk",
"wv", "wo": {"kernel", "bias"}}, "mlp": {"dense_h_to_4h", "dense_4h_to_h"}},
"final_norm", "embed_out": {"kernel"}}}`, kernels `[in, out]`.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def param_counts(c: dict) -> dict:
    """Parameter counts of `GPTNeoXForCausalLM` (un-tied head)."""
    h, f, v, layers = c["hidden_size"], c["intermediate_size"], c["vocab_size"], c["num_hidden_layers"]
    per_layer = 4 * (h * h + h) + (h * f + f) + (f * h + h) + 2 * 2 * h
    return {"embedding": v * h, "layers": layers * per_layer, "final_norm": 2 * h,
            "head": h * v, "total": v * h + layers * per_layer + 2 * h + h * v}


def init_params(config: dict, key, dtype=jnp.bfloat16):
    """Every weight from `key`, on the device, in one jitted call, in the type
    it is served in. The distribution is `config["init"]` (see the
    configuration file's `assumed`): plain normal kernels."""
    return _init(key, _Sizes.of(config), jnp.dtype(dtype).name)


class _Sizes(NamedTuple):
    """The numbers `init_params` and the forward pass need, hashable for jit."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    rotary_pct: float
    rotary_emb_base: float
    layer_norm_eps: float
    init_std: float

    @classmethod
    def of(cls, config: dict) -> "_Sizes":
        flat = dict(config, init_std=config.get("init", {}).get("std", 0.02))
        return cls(*(flat[f] for f in cls._fields))

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def rotary_ndims(self) -> int:
        return int(self.head_dim * self.rotary_pct)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _init(key, s: _Sizes, dtype: str):
    dtype = jnp.dtype(dtype)
    h, f, v = s.hidden_size, s.intermediate_size, s.vocab_size
    counter = iter(range(1 << 20))

    def normal(shape, std=s.init_std):
        k = jax.random.fold_in(key, next(counter))
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    def dense(n_in, n_out):
        return {"kernel": normal((n_in, n_out)), "bias": normal((n_out,))}

    def norm():
        return {"scale": (1.0 + normal((h,)).astype(jnp.float32)).astype(dtype), "bias": normal((h,))}

    params = {"embed_in": {"embedding": normal((v, h))}}
    for i in range(s.num_hidden_layers):
        params[f"layer_{i}"] = {
            "input_norm": norm(),
            "post_attn_norm": norm(),
            "attention": {"wq": dense(h, h), "wk": dense(h, h),
                          "wv": dense(h, h), "wo": dense(h, h)},
            "mlp": {"dense_h_to_4h": dense(h, f), "dense_4h_to_h": dense(f, h)},
        }
    params["final_norm"] = norm()
    params["embed_out"] = {"kernel": normal((h, v))}
    return {"params": params}


# --------------------------------------------------------------- the forward pass
def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def rotary(x, positions, ndims: int, base: float):
    """x: [b, t, heads, head_dim]; rotate the first `ndims` dims, half-split."""
    rot, rest = x[..., :ndims], x[..., ndims:]
    inv_freq = 1.0 / (base ** (jnp.arange(0, ndims, 2, dtype=jnp.float32) / ndims))
    angles = positions[:, :, None, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = rot[..., : ndims // 2], rot[..., ndims // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def dense(x, p):
    return x @ p["kernel"] + p["bias"]


@functools.partial(jax.jit, static_argnums=(2,))
def block(p, x, s: _Sizes):
    """One layer, float32, matmul precision "highest". x: [b, t, hidden]."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        b, t, _ = x.shape
        heads, d = s.num_attention_heads, s.head_dim
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
        a = layer_norm(x, p["input_norm"], s.layer_norm_eps)
        q = dense(a, p["attention"]["wq"]).reshape(b, t, heads, d)
        k = dense(a, p["attention"]["wk"]).reshape(b, t, heads, d)
        v = dense(a, p["attention"]["wv"]).reshape(b, t, heads, d)
        q = rotary(q, positions, s.rotary_ndims, s.rotary_emb_base)
        k = rotary(k, positions, s.rotary_ndims, s.rotary_emb_base)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
        causal = jnp.tril(jnp.ones((t, t), bool))
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(b, t, heads * d)
        attn = dense(attn, p["attention"]["wo"])
        m = layer_norm(x, p["post_attn_norm"], s.layer_norm_eps)
        m = dense(gelu(dense(m, p["mlp"]["dense_h_to_4h"])), p["mlp"]["dense_4h_to_h"])
        return x + attn + m


@functools.partial(jax.jit, static_argnums=(3,))
def head_gaps(final_norm, embed_out, x, s: _Sizes, tokens):
    """For each row of x [b, n, hidden]: how far the logit of `tokens` [b, n]
    lies below the best logit. 0 where the token is the reference's own choice."""
    with jax.default_matmul_precision("highest"):
        x = layer_norm(x, _f32(final_norm), s.layer_norm_eps)
        logits = x @ embed_out["kernel"].astype(jnp.float32)
        chosen = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
        return logits.max(-1) - chosen


def hidden_states(params, config: dict, ids):
    """ids [b, t] -> the last layer's output [b, t, hidden], layer by layer so
    that only one layer's float32 copy is alive at a time."""
    s = _Sizes.of(config)
    inner = params["params"]
    x = inner["embed_in"]["embedding"][ids].astype(jnp.float32)
    for i in range(s.num_hidden_layers):
        x = block(inner[f"layer_{i}"], x, s)
    return x


def logits(params, config: dict, ids):
    """Full logits [b, t, vocab]; for tests at small sizes."""
    s = _Sizes.of(config)
    x = hidden_states(params, config, ids)
    with jax.default_matmul_precision("highest"):
        x = layer_norm(x, _f32(params["params"]["final_norm"]), s.layer_norm_eps)
        return x @ params["params"]["embed_out"]["kernel"].astype(jnp.float32)


def served_token_gaps(params, config: dict, served: list, pad_to: int, rows: int, batch: int = 4) -> list:
    """Teacher-forced check of served requests. `served` is a list of
    `(prompt_ids, generated_tokens)`; each is run once through the reference as
    prompt + generated[:-1], right-padded to `pad_to` (causal: pads are never
    seen), and every generated token is held against the reference's best logit
    at its position. Returns one float array of gaps a request. `rows` bounds
    the generated tokens of one request (the head is computed on that many
    positions)."""
    s = _Sizes.of(config)
    inner = params["params"]
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
            head_gaps(inner["final_norm"], inner["embed_out"], x, s, jnp.asarray(tokens))))
        for j, (_prompt, generated) in enumerate(group):
            out.append(gaps[j, : len(generated)])
    return out
