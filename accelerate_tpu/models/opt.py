"""OPT family decoder in flax — the reference's 30B big-model-inference config
(benchmarks/README.md:36-37: OPT-30B, 2.37 s/token fp16 CPU-offload / 33.9 s/token
fp32 disk-offload on 2x Titan RTX). The CPU/disk-offload rows are exactly the tiered
execution big_modeling.py replaces with overlapped layer streaming.

Architecture: pre-LN transformer with LEARNED position embeddings (with OPT's
historical +2 index offset), biased q/k/v/out and fc1/fc2, ReLU activation, and the
lm_head tied to the token embedding."""

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

OPT_SHARDING_RULES = [
    (r"(wq|wk|wv)/kernel", (None, "model")),
    (r"wo/kernel", ("model", None)),
    (r"fc1/kernel", (None, "model")),
    (r"fc2/kernel", ("model", None)),
    (r"embed_tokens/embedding", ("model", None)),
]

# OPT's learned position table is indexed at position+2 (a legacy of fairseq's
# padding-token bookkeeping); the table itself has max_position_embeddings + 2 rows.
POSITION_OFFSET = 2


@dataclass
class OPTConfig:
    vocab_size: int = 50272
    hidden_size: int = 7168
    intermediate_size: int = 28672
    num_hidden_layers: int = 48
    num_attention_heads: int = 56
    max_position_embeddings: int = 2048
    layer_norm_eps: float = 1e-5
    scan_layers: bool = False
    decode_cache_length: int = 0
    param_dtype: str = "float32"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def _pdtype(self):
        return jnp.dtype(self.param_dtype)


class OPTAttention(nn.Module):
    config: OPTConfig

    @nn.compact
    def __call__(self, hidden, positions, mask):
        cfg = self.config
        b, s, _ = hidden.shape
        h, d = cfg.num_attention_heads, cfg.head_dim
        q = nn.Dense(h * d, param_dtype=cfg._pdtype, name="wq")(hidden).reshape(b, s, h, d)
        k = nn.Dense(h * d, param_dtype=cfg._pdtype, name="wk")(hidden).reshape(b, s, h, d)
        v = nn.Dense(h * d, param_dtype=cfg._pdtype, name="wv")(hidden).reshape(b, s, h, d)

        if cfg.decode_cache_length:
            L = cfg.decode_cache_length
            k_all, v_all, decode_mask = update_decode_cache(self, k, v, L, pad_mask=mask)
            out = dot_product_attention(q, k_all, v_all, mask=decode_mask, causal=False)
        else:
            out = dot_product_attention(q, k, v, mask=mask, causal=True)
        return nn.Dense(cfg.hidden_size, param_dtype=cfg._pdtype, name="wo")(out.reshape(b, s, h * d))


class OPTBlock(nn.Module):
    config: OPTConfig

    @nn.compact
    def __call__(self, hidden, positions, mask):
        cfg = self.config
        # Pre-LN (do_layer_norm_before=True, the configuration of every OPT >= 350m).
        attn = OPTAttention(cfg, name="attention")(
            nn.LayerNorm(epsilon=cfg.layer_norm_eps, param_dtype=cfg._pdtype, name="self_attn_norm")(hidden),
            positions,
            mask,
        )
        hidden = constrain_activation(hidden + attn)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, param_dtype=cfg._pdtype, name="final_norm")(hidden)
        x = nn.relu(nn.Dense(cfg.intermediate_size, param_dtype=cfg._pdtype, name="fc1")(x))
        x = nn.Dense(cfg.hidden_size, param_dtype=cfg._pdtype, name="fc2")(x)
        return constrain_activation(hidden + x)


class _ScanBlockBody(nn.Module):
    config: OPTConfig

    @nn.compact
    def __call__(self, carry, positions, mask):
        return OPTBlock(self.config, name="block")(carry, positions, mask), None


class OPTForCausalLM(nn.Module):
    config: OPTConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, positions=None, logits_at=None):
        cfg = self.config
        b, s = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, param_dtype=cfg._pdtype, name="embed_tokens")
        pos_embed = nn.Embed(
            cfg.max_position_embeddings + POSITION_OFFSET,
            cfg.hidden_size,
            param_dtype=cfg._pdtype,
            name="embed_positions",
        )
        hidden = constrain_activation(embed(input_ids) + pos_embed(positions + POSITION_OFFSET))
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
            Block = maybe_remat(OPTBlock)
            for i in range(cfg.num_hidden_layers):
                hidden = Block(cfg, name=f"layer_{i}")(hidden, positions, attention_mask)
        hidden = nn.LayerNorm(epsilon=cfg.layer_norm_eps, param_dtype=cfg._pdtype, name="final_norm")(
            rows_for_head(hidden, logits_at))
        # Tied head: logits against the token embedding (OPT ties by default).
        embedding = self.variables["params"]["embed_tokens"]["embedding"]
        return hidden @ embedding.T.astype(hidden.dtype)


def create_opt_model(
    config: Optional[OPTConfig] = None, rng=None, seq_len: int = 2048, param_dtype=None
) -> Model:
    import dataclasses

    config = config or opt_tiny()
    if param_dtype is not None:
        config = dataclasses.replace(config, param_dtype=str(jnp.dtype(param_dtype)))
    if rng is None:
        rng = jax.random.key(0)
    module = OPTForCausalLM(config)
    sample = jnp.zeros((1, min(seq_len, config.max_position_embeddings)), dtype=jnp.int32)
    params = jax.jit(module.init)(rng, sample)
    return Model.from_flax(module, params, loss_fn=causal_lm_loss, sharding_rules=OPT_SHARDING_RULES)


class OPTLayeredApply:
    """LayeredApply protocol for tier-streamed execution of the 30B config
    (the reference's CPU/disk-offload benchmark rows)."""

    def __init__(self, config: OPTConfig):
        self.config = config

    def _layer_names(self, params):
        inner = params["params"]
        return sorted((k for k in inner if k.startswith("layer_")), key=lambda s: int(s.split("_")[1]))

    def split(self, params):
        inner = params["params"]
        prelude = {"params": {k: inner[k] for k in ("embed_tokens", "embed_positions")}}
        if "blocks" in inner:
            stacked = inner["blocks"]["block"]
            layers = [
                {"params": jax.tree_util.tree_map(lambda x: x[i], stacked)}
                for i in range(self.config.num_hidden_layers)
            ]
        else:
            layers = [{"params": inner[name]} for name in self._layer_names(params)]
        # Tied head: the tail re-uses the embedding from the prelude, so split()
        # duplicates the reference into both (join() keeps one copy).
        tail = {"params": {"final_norm": inner["final_norm"], "embed_tokens": inner["embed_tokens"]}}
        return prelude, layers, tail

    def join(self, prelude, layers, tail):
        inner = dict(prelude["params"])
        for i, lp in enumerate(layers):
            inner[f"layer_{i}"] = lp["params"]
        inner["final_norm"] = tail["params"]["final_norm"]
        return {"params": inner}

    def apply_prelude(self, prelude_params, input_ids, attention_mask=None):
        cfg = self.config
        b, s = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        inner = prelude_params["params"]
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size).apply(
            {"params": {"embedding": inner["embed_tokens"]["embedding"]}}, input_ids
        )
        pos = nn.Embed(cfg.max_position_embeddings + POSITION_OFFSET, cfg.hidden_size).apply(
            {"params": {"embedding": inner["embed_positions"]["embedding"]}}, positions + POSITION_OFFSET
        )
        return (embed + pos, positions, attention_mask)

    def apply_layer(self, layer_params, carry):
        hidden, positions, mask = carry
        hidden = OPTBlock(self.config).apply(layer_params, hidden, positions, mask)
        return (hidden, positions, mask)

    def apply_tail(self, tail_params, carry):
        cfg = self.config
        hidden, _, _ = carry
        hidden = nn.LayerNorm(epsilon=cfg.layer_norm_eps).apply(
            {"params": tail_params["params"]["final_norm"]}, hidden
        )
        embedding = tail_params["params"]["embed_tokens"]["embedding"]
        return hidden @ embedding.T.astype(hidden.dtype)


def opt_30b() -> OPTConfig:
    """facebook/opt-30b dims (reference benchmarks/README.md:36-37)."""
    return OPTConfig()


def opt_tiny() -> OPTConfig:
    return OPTConfig(
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_hidden_layers=2,
        num_attention_heads=4,
        max_position_embeddings=256,
    )
