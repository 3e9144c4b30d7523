"""BERT for sequence classification in plain `jax.numpy`: the seeded weights,
the float32 forward pass, its loss and gradients, and plain AdamW steps — what
decides `correct` for the cells that fine-tune this family.

Follows `BertForSequenceClassification` as published: word + position + token
type embeddings, LayerNorm; per layer self-attention, `LayerNorm(x + attn)`,
GELU MLP, `LayerNorm(x + mlp)` (post-norm); tanh pooler on the first token; a
linear classifier; mean softmax cross-entropy. No masks (the cells' rows have no
padding), no dropout (the program trains without it), nothing imported from the
program. Departures, each the layout the seeded weights are made in: q, k and v
are one `[hidden, 3*hidden]` matrix whose thirds are q, k, v; GELU is the exact
erf form the config publishes (`hidden_act: gelu`).

`precision` selects how matrix products are computed: "float32" (operands as
they are, precision "highest") is the reference; "bfloat16" and
"float8_e4m3fn" round both operands of every product to that type, and the
gradients that flow back through them to its gradient type (`_rounder`), and are
the CONTROLS: the reference put in the program's place at the program's
precision and at the next one down.

Weights: `{"params": {"bert": {"word_embeddings", "position_embeddings",
"token_type_embeddings": {"embedding"}, "embeddings_ln", "layer_<i>":
{"attention": {"qkv", "attn_out"}, "attn_ln", "mlp_up", "mlp_down", "mlp_ln"},
"pooler"}, "classifier"}}`, kernels `[in, out]`, norms `{"scale", "bias"}`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

ADAMW = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 1e-4}  # optax.adamw's defaults


def param_counts(c: dict) -> dict:
    """Parameter counts of BERT with a pooler and a `num_labels`-way classifier."""
    h, f, layers = c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"]
    embedding = (c["vocab_size"] + c["max_position_embeddings"] + c["type_vocab_size"]) * h + 2 * h
    per_layer = 4 * (h * h + h) + (h * f + f) + (f * h + h) + 2 * 2 * h
    head = (h * h + h) + (h * c.get("num_labels", 2) + c.get("num_labels", 2))
    return {"embedding": embedding, "layers": layers * per_layer, "head": head,
            "total": embedding + layers * per_layer + head}


def init_params(config: dict, key, dtype=jnp.float32):
    """Every weight from `key`, on the device, in one jitted call."""
    sizes = tuple(config[k] for k in _FIELDS)
    return _init(key, sizes, config.get("init", {}).get("std", 0.02), jnp.dtype(dtype).name)


_FIELDS = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
           "intermediate_size", "max_position_embeddings", "type_vocab_size", "num_labels")


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _init(key, sizes, std, dtype):
    v, h, layers, _heads, f, positions, types, labels = sizes
    dtype = jnp.dtype(dtype)
    counter = iter(range(1 << 20))

    def normal(shape):
        k = jax.random.fold_in(key, next(counter))
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    def dense(n_in, n_out):
        return {"kernel": normal((n_in, n_out)), "bias": normal((n_out,))}

    def norm():
        return {"scale": (1.0 + normal((h,))).astype(dtype), "bias": normal((h,))}

    bert = {
        "word_embeddings": {"embedding": normal((v, h))},
        "position_embeddings": {"embedding": normal((positions, h))},
        "token_type_embeddings": {"embedding": normal((types, h))},
        "embeddings_ln": norm(),
        "pooler": dense(h, h),
    }
    for i in range(layers):
        bert[f"layer_{i}"] = {
            "attention": {"qkv": dense(h, 3 * h), "attn_out": dense(h, h)},
            "attn_ln": norm(), "mlp_up": dense(h, f), "mlp_down": dense(f, h), "mlp_ln": norm(),
        }
    return {"params": {"bert": bert, "classifier": dense(h, labels)}}


# --------------------------------------------------------------- the forward pass
#: The type gradients travel in where the forward pass is in the key's type
#: (float8 training keeps e4m3 for activations and weights, e5m2 for gradients).
COTANGENT_TYPE = {"bfloat16": "bfloat16", "float8_e4m3fn": "float8_e5m2"}


def _to(x, low):
    """Round a float32 tensor to `low` and back. float8 gets the per-tensor
    scale (largest magnitude onto the type's largest value) float8 training uses."""
    if low == jnp.bfloat16:
        return x.astype(low).astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(low).max)
    return (x / scale).astype(low).astype(jnp.float32) * scale


def _rounder(precision: str):
    """Both operands of every product rounded to `precision` on the way forward,
    and the gradient that comes back through them rounded to its
    `COTANGENT_TYPE`: what a training path in that precision does."""
    forward_type, backward_type = jnp.dtype(precision), jnp.dtype(COTANGENT_TYPE[precision])

    @jax.custom_vjp
    def rounded(x):
        return _to(x, forward_type)

    rounded.defvjp(lambda x: (_to(x, forward_type), None), lambda _, g: (_to(g, backward_type),))
    return rounded


def _matmul(precision: str):
    if precision == "float32":
        return lambda a, b: jnp.matmul(a, b, precision="highest")
    rounded = _rounder(precision)
    return lambda a, b: jnp.matmul(rounded(a), rounded(b), precision="highest")


def layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def forward(params, config: dict, batch: dict, precision: str = "float32"):
    """Classifier logits [b, num_labels]. The layers are one `lax.scan` over
    their stacked weights: the same arithmetic as a Python loop, compiled once."""
    mm = _matmul(precision)
    p = params["params"]["bert"]
    eps, heads = config["layer_norm_eps"], config["num_attention_heads"]
    ids = batch["input_ids"]
    b, t = ids.shape
    d = config["hidden_size"] // heads
    x = (p["word_embeddings"]["embedding"][ids]
         + p["position_embeddings"]["embedding"][jnp.arange(t)][None]
         + p["token_type_embeddings"]["embedding"][batch["token_type_ids"]])
    x = layer_norm(x, p["embeddings_ln"], eps)

    def layer(x, lp):
        qkv = mm(x, lp["attention"]["qkv"]["kernel"]) + lp["attention"]["qkv"]["bias"]
        q, k, v = (part.reshape(b, t, heads, d).transpose(0, 2, 1, 3) for part in jnp.split(qkv, 3, axis=-1))
        weights = jax.nn.softmax(mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(d), axis=-1)
        attn = mm(weights, v).transpose(0, 2, 1, 3).reshape(b, t, heads * d)
        attn = mm(attn, lp["attention"]["attn_out"]["kernel"]) + lp["attention"]["attn_out"]["bias"]
        x = layer_norm(x + attn, lp["attn_ln"], eps)
        up = gelu(mm(x, lp["mlp_up"]["kernel"]) + lp["mlp_up"]["bias"])
        down = mm(up, lp["mlp_down"]["kernel"]) + lp["mlp_down"]["bias"]
        return layer_norm(x + down, lp["mlp_ln"], eps), None

    layers = [p[f"layer_{i}"] for i in range(config["num_hidden_layers"])]
    stacked = jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *layers)
    x, _ = jax.lax.scan(layer, x, stacked)
    pooled = jnp.tanh(mm(x[:, 0], p["pooler"]["kernel"]) + p["pooler"]["bias"])
    head = params["params"]["classifier"]
    return mm(pooled, head["kernel"]) + head["bias"]


def loss(params, config: dict, batch: dict, precision: str = "float32"):
    logits = forward(params, config, batch, precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1)[:, 0].mean()


def leaf_norms(tree) -> dict:
    """`{"/".join(path): l2 norm}` of every leaf."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path): jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for path, x in flat}


#: Rows the loss and its gradient are computed on at a time: the batch mean is
#: the mean of equal blocks' means, and a float32 block of 32 x 128 tokens keeps
#: the reference's activations (1.1 GB) beside the program's state on one chip.
BLOCK_ROWS = 32


def train_steps(params, config: dict, batches: list, learning_rate: float, precision: str = "float32") -> dict:
    """Plain AdamW (optax.adamw's defaults, decay on every leaf) from `params`
    over `batches`. Returns each step's loss, the first gradient (the tree, and
    its norm a leaf), and the norm a leaf of the parameters' change over all
    the steps."""
    frozen = tuple(sorted((k, v) for k, v in config.items() if isinstance(v, (int, float, str, bool))))
    start = params = _f32(params)
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for step, batch in enumerate(batches, start=1):
        params, mu, nu, value, grads = _adamw_step(
            params, mu, nu, batch, jnp.float32(step), frozen, float(learning_rate), precision)
        losses.append(value)
        if first_grad is None:
            first_grad = grads
    norms = jax.jit(leaf_norms)
    change = norms(jax.tree_util.tree_map(lambda a, b: a - b, params, start))
    return jax.device_get({"losses": jnp.stack(losses), "first_grad": first_grad,
                           "first_grad_norms": norms(first_grad), "change_norms": change})


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _adamw_step(params, mu, nu, batch, step, frozen_config, lr, precision):
    config = dict(frozen_config)
    b1, b2, eps, wd = ADAMW["b1"], ADAMW["b2"], ADAMW["eps"], ADAMW["weight_decay"]
    rows = len(batch["labels"])
    blocks = max(rows // BLOCK_ROWS, 1)
    if rows % blocks:
        raise ValueError(f"a batch of {rows} rows does not split into equal blocks")
    blocked = jax.tree_util.tree_map(lambda x: x.reshape((blocks, rows // blocks) + x.shape[1:]), batch)

    def add_block(total, block):
        value, grads = jax.value_and_grad(loss)(params, config, block, precision)
        return jax.tree_util.tree_map(jnp.add, total, (value, grads)), None

    zero = (jnp.float32(0.0), jax.tree_util.tree_map(jnp.zeros_like, params))
    (value, grads), _ = jax.lax.scan(add_block, zero, blocked)
    value, grads = jax.tree_util.tree_map(lambda x: x / blocks, (value, grads))
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda n, g: b2 * n + (1 - b2) * g * g, nu, grads)
    params = jax.tree_util.tree_map(
        lambda p, m, n: p - lr * ((m / (1 - b1 ** step)) / (jnp.sqrt(n / (1 - b2 ** step)) + eps) + wd * p),
        params, mu, nu)
    return params, mu, nu, value, grads
