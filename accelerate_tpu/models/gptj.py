"""GPT-J family decoder in flax — the reference's big-model-inference headline
architecture (benchmarks/README.md:31: GPT-J-6B fp16, 0.05 s/token on 2x Titan RTX;
driver benchmarks/big_model_inference.py). Implementing it natively lets bench.py's
inference mode measure the SAME model configuration the reference publishes.

Architecture (vs Llama): parallel residual block — `x + attn(ln(x)) + mlp(ln(x))`
with ONE LayerNorm per block; partial rotary (first `rotary_dim` dims of each head);
standard LayerNorm with bias; biased MLP + lm_head, un-biased QKV/out projections;
full multi-head attention (no GQA). Shares the attention seam (`ops/attention`) and
the KV-cache pattern with the Llama family, so decode/flash dispatch and the
Generator work unchanged.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..modeling import Model
from ..ops.attention import dot_product_attention, update_decode_cache
from ..parallel.sharding import constrain_activation
from ..ops.remat import maybe_remat
from .llama import causal_lm_loss, rows_for_head

GPTJ_SHARDING_RULES = [
    (r"(wq|wk|wv)/kernel", (None, "model")),
    (r"wo/kernel", ("model", None)),
    (r"fc_in/kernel", (None, "model")),
    (r"fc_out/kernel", ("model", None)),
    (r"wte/embedding", ("model", None)),
    (r"lm_head/kernel", (None, "model")),
]


@dataclass
class GPTJConfig:
    vocab_size: int = 50400
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 28
    num_attention_heads: int = 16
    rotary_dim: int = 64
    max_position_embeddings: int = 2048
    layer_norm_eps: float = 1e-5
    scan_layers: bool = False
    decode_cache_length: int = 0  # same contract as LlamaConfig
    # Parameter STORAGE dtype. "bfloat16" initializes params directly in bf16 —
    # required to even instantiate gptj_6b on a 16GB-HBM chip (an f32 init tree
    # would be 24GB before any cast).
    param_dtype: str = "float32"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def _pdtype(self):
        return jnp.dtype(self.param_dtype)


def partial_rotary(x, positions, rotary_dim: int):
    """GPT-J RoPE variant: rotate only the first `rotary_dim` dims of each head,
    pass the rest through. GPT-J interleaves even/odd dims (rotate_every_two)
    rather than splitting in halves like Llama."""
    rot, pass_through = x[..., :rotary_dim], x[..., rotary_dim:]
    inv_freq = 1.0 / (10000.0 ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim))
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, S, rot/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1 = rot.astype(jnp.float32)[..., ::2]
    x2 = rot.astype(jnp.float32)[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(rot.shape)
    return jnp.concatenate([out.astype(x.dtype), pass_through], axis=-1)


class GPTJAttention(nn.Module):
    config: GPTJConfig

    @nn.compact
    def __call__(self, hidden, positions, mask):
        cfg = self.config
        b, s, _ = hidden.shape
        h, d = cfg.num_attention_heads, cfg.head_dim
        q = nn.Dense(h * d, use_bias=False, param_dtype=cfg._pdtype, name="wq")(hidden).reshape(b, s, h, d)
        k = nn.Dense(h * d, use_bias=False, param_dtype=cfg._pdtype, name="wk")(hidden).reshape(b, s, h, d)
        v = nn.Dense(h * d, use_bias=False, param_dtype=cfg._pdtype, name="wv")(hidden).reshape(b, s, h, d)
        q = partial_rotary(q, positions, cfg.rotary_dim)
        k = partial_rotary(k, positions, cfg.rotary_dim)

        if cfg.decode_cache_length:
            L = cfg.decode_cache_length
            k_all, v_all, decode_mask = update_decode_cache(self, k, v, L, pad_mask=mask)
            out = dot_product_attention(q, k_all, v_all, mask=decode_mask, causal=False)
        else:
            out = dot_product_attention(q, k, v, mask=mask, causal=True)
        return nn.Dense(cfg.hidden_size, use_bias=False, param_dtype=cfg._pdtype, name="wo")(out.reshape(b, s, h * d))


class GPTJMLP(nn.Module):
    config: GPTJConfig

    @nn.compact
    def __call__(self, hidden):
        cfg = self.config
        return nn.Dense(cfg.hidden_size, param_dtype=cfg._pdtype, name="fc_out")(
            nn.gelu(nn.Dense(cfg.intermediate_size, param_dtype=cfg._pdtype, name="fc_in")(hidden))
        )


class GPTJBlock(nn.Module):
    config: GPTJConfig

    @nn.compact
    def __call__(self, hidden, positions, mask):
        cfg = self.config
        # Parallel residual: one LN feeds BOTH branches; their outputs add to the
        # residual stream together (GPT-J's signature structure).
        normed = nn.LayerNorm(epsilon=cfg.layer_norm_eps, param_dtype=cfg._pdtype, name="ln_1")(hidden)
        attn = GPTJAttention(cfg, name="attention")(normed, positions, mask)
        mlp = GPTJMLP(cfg, name="mlp")(normed)
        return constrain_activation(hidden + attn + mlp)


class _ScanBlockBody(nn.Module):
    config: GPTJConfig

    @nn.compact
    def __call__(self, carry, positions, mask):
        return GPTJBlock(self.config, name="block")(carry, positions, mask), None


class GPTJForCausalLM(nn.Module):
    config: GPTJConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, positions=None, logits_at=None):
        cfg = self.config
        b, s = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        hidden = constrain_activation(
            nn.Embed(cfg.vocab_size, cfg.hidden_size, param_dtype=cfg._pdtype, name="wte")(input_ids)
        )
        if cfg.scan_layers:
            scan_block = nn.scan(
                maybe_remat(_ScanBlockBody),
                variable_axes={"params": 0, "cache": 0},
                split_rngs={"params": True},
                in_axes=(nn.broadcast, nn.broadcast),
                length=cfg.num_hidden_layers,
            )
            hidden, _ = scan_block(cfg, name="blocks")(hidden, positions, attention_mask)
        else:
            Block = maybe_remat(GPTJBlock)
            for i in range(cfg.num_hidden_layers):
                hidden = Block(cfg, name=f"layer_{i}")(hidden, positions, attention_mask)
        hidden = nn.LayerNorm(epsilon=cfg.layer_norm_eps, param_dtype=cfg._pdtype, name="ln_f")(
            rows_for_head(hidden, logits_at))
        return nn.Dense(cfg.vocab_size, param_dtype=cfg._pdtype, name="lm_head")(hidden)  # biased, per GPT-J


def create_gptj_model(
    config: Optional[GPTJConfig] = None, rng=None, seq_len: int = 2048, param_dtype=None
) -> Model:
    import dataclasses

    config = config or gptj_tiny()
    if param_dtype is not None:
        # Threaded into the module (not cast after init) so a 6B model never
        # materializes an f32 tree: peak init memory is the bf16 params plus one
        # f32 temp for the largest single param.
        config = dataclasses.replace(config, param_dtype=str(jnp.dtype(param_dtype)))
    if rng is None:
        rng = jax.random.key(0)
    module = GPTJForCausalLM(config)
    sample = jnp.zeros((1, min(seq_len, config.max_position_embeddings)), dtype=jnp.int32)
    params = jax.jit(module.init)(rng, sample)
    return Model.from_flax(module, params, loss_fn=causal_lm_loss, sharding_rules=GPTJ_SHARDING_RULES)


class GPTJLayeredApply:
    """LayeredApply protocol for layer-streamed big-model inference (same protocol
    as LlamaLayeredApply): runs GPT-J/NeoX-class models larger than HBM by
    streaming one block's weights at a time."""

    def __init__(self, config: GPTJConfig):
        self.config = config

    def _layer_names(self, params):
        inner = params["params"]
        return sorted(
            (k for k in inner if k.startswith("layer_")),
            key=lambda s: int(s.split("_")[1]),
        )

    def split(self, params):
        inner = params["params"]
        prelude = {"params": {"wte": inner["wte"]}}
        if "blocks" in inner:
            stacked = inner["blocks"]["block"]
            layers = [
                {"params": jax.tree_util.tree_map(lambda x: x[i], stacked)}
                for i in range(self.config.num_hidden_layers)
            ]
        else:
            layers = [{"params": inner[name]} for name in self._layer_names(params)]
        tail = {"params": {k: inner[k] for k in ("ln_f", "lm_head") if k in inner}}
        return prelude, layers, tail

    def join(self, prelude, layers, tail):
        inner = dict(prelude["params"])
        for i, lp in enumerate(layers):
            inner[f"layer_{i}"] = lp["params"]
        inner.update(tail["params"])
        return {"params": inner}

    def apply_prelude(self, prelude_params, input_ids, attention_mask=None):
        cfg = self.config
        b, s = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        hidden = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="wte").apply(
            {"params": {"embedding": prelude_params["params"]["wte"]["embedding"]}}, input_ids
        )
        return (hidden, positions, attention_mask)

    def apply_layer(self, layer_params, carry):
        hidden, positions, mask = carry
        hidden = GPTJBlock(self.config).apply(layer_params, hidden, positions, mask)
        return (hidden, positions, mask)

    def apply_tail(self, tail_params, carry):
        cfg = self.config
        hidden, _, _ = carry
        hidden = nn.LayerNorm(epsilon=cfg.layer_norm_eps).apply(
            {"params": tail_params["params"]["ln_f"]}, hidden
        )
        return nn.Dense(cfg.vocab_size).apply({"params": tail_params["params"]["lm_head"]}, hidden)


def gptj_6b() -> GPTJConfig:
    """EleutherAI GPT-J-6B dims (the reference's benchmarks/README.md:31 headline)."""
    return GPTJConfig()


def gptj_tiny() -> GPTJConfig:
    """Test-size config."""
    return GPTJConfig(
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_hidden_layers=2,
        num_attention_heads=4,
        rotary_dim=16,
        max_position_embeddings=256,
    )
