"""GPT-NeoX family decoder in flax — the reference's 20B big-model-inference config
(benchmarks/README.md:33-34: GPT-NeoX-20B, 0.08 s/token fp16 / 10.72 s/token fp32
disk-offload on 2x Titan RTX). The 20B size is the flagship case for layer-streamed
execution (big_modeling.py): 40GB of bf16 weights against 16GB of HBM.

Architecture: parallel residual `x + attn(ln_1(x)) + mlp(ln_2(x))` with TWO
LayerNorms per block (vs GPT-J's one); partial rotary in Llama's half-split style
(rotary_pct of each head, NOT GPT-J's interleaved pairs); biased QKV/out/MLP
projections; un-biased lm_head (embed_out)."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..modeling import Model
from ..ops.attention import (
    dot_product_attention,
    slot_cache_attention,
    update_decode_cache,
)
from ..parallel.sharding import constrain_activation
from ..ops.remat import maybe_remat
from .llama import ServedConfig, causal_lm_loss, rows_for_head

# Parity oracle for the sharding planner (see LLAMA_SHARDING_RULES).
GPT_NEOX_SHARDING_RULES = [
    (r"(wq|wk|wv)/kernel", (None, "model")),
    (r"wo/kernel", ("model", None)),
    (r"dense_h_to_4h/kernel", (None, "model")),
    (r"dense_4h_to_h/kernel", ("model", None)),
    (r"embed_in/embedding", ("model", None)),
    (r"embed_out/kernel", (None, "model")),
]


@dataclass
class GPTNeoXConfig(ServedConfig):
    vocab_size: int = 50432
    hidden_size: int = 6144
    intermediate_size: int = 24576
    num_hidden_layers: int = 44
    num_attention_heads: int = 64
    rotary_pct: float = 0.25
    rope_theta: float = 10000.0
    max_position_embeddings: int = 2048
    layer_norm_eps: float = 1e-5
    use_parallel_residual: bool = True
    scan_layers: bool = False
    # Serving, beyond `ServedConfig` (see LlamaConfig for each): a quantized
    # page pool, int8 weights, a tensor-parallel engine.
    decode_kv_cache_dtype: str = "bf16"
    weight_dtype: str = "bf16"
    decode_tp_mesh: Optional[Any] = None
    param_dtype: str = "float32"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def rotary_ndims(self) -> int:
        return int(self.head_dim * self.rotary_pct)

    @property
    def _pdtype(self):
        return jnp.dtype(self.param_dtype)


def neox_partial_rotary(x, positions, rotary_ndims: int, theta: float):
    """NeoX RoPE: rotate the first `rotary_ndims` dims of each head in the
    HALF-SPLIT style (rotate_half, like Llama), pass the rest through."""
    rot, pass_through = x[..., :rotary_ndims], x[..., rotary_ndims:]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotary_ndims, 2, dtype=jnp.float32) / rotary_ndims))
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(rot.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), pass_through], axis=-1)


class GPTNeoXAttention(nn.Module):
    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, hidden, positions, mask):
        cfg = self.config
        b, s, _ = hidden.shape
        h, d = cfg.num_attention_heads, cfg.head_dim
        q = nn.Dense(h * d, param_dtype=cfg._pdtype, name="wq")(hidden).reshape(b, s, h, d)
        k = nn.Dense(h * d, param_dtype=cfg._pdtype, name="wk")(hidden).reshape(b, s, h, d)
        v = nn.Dense(h * d, param_dtype=cfg._pdtype, name="wv")(hidden).reshape(b, s, h, d)
        q = neox_partial_rotary(q, positions, cfg.rotary_ndims, cfg.rope_theta)
        k = neox_partial_rotary(k, positions, cfg.rotary_ndims, cfg.rope_theta)

        if cfg.decode_cache_length:
            L = cfg.decode_cache_length
            if cfg.decode_slot_cache:
                # Continuous-batching decode: per-row scatter writes at each
                # slot's own position (serving.ContinuousBatcher). `mask` is
                # the [B, pages_per_slot] int32 page table;
                # decode_attention_impl picks the XLA live-page read or the fused
                # Pallas page-walk kernels.
                out = slot_cache_attention(
                    self, q, k, v, L, positions,
                    page_table=mask,
                    page_size=cfg.decode_page_size,
                    num_pages=cfg.decode_num_pages,
                    attention_impl=cfg.decode_attention_impl,
                    kv_cache_dtype=cfg.decode_kv_cache_dtype,
                    mesh=cfg.decode_tp_mesh,
                )
            else:
                k_all, v_all, decode_mask = update_decode_cache(self, k, v, L, pad_mask=mask)
                out = dot_product_attention(q, k_all, v_all, mask=decode_mask, causal=False)
        else:
            out = dot_product_attention(q, k, v, mask=mask, causal=True)
        return nn.Dense(cfg.hidden_size, param_dtype=cfg._pdtype, name="wo")(out.reshape(b, s, h * d))


class GPTNeoXMLP(nn.Module):
    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, hidden):
        cfg = self.config
        # exact (erf) gelu: NeoX's hidden_act is "gelu", not the tanh "gelu_new"
        # GPT-J uses — approximate=True here would drift from the HF reference.
        return nn.Dense(cfg.hidden_size, param_dtype=cfg._pdtype, name="dense_4h_to_h")(
            nn.gelu(
                nn.Dense(cfg.intermediate_size, param_dtype=cfg._pdtype, name="dense_h_to_4h")(hidden),
                approximate=False,
            )
        )


class GPTNeoXBlock(nn.Module):
    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, hidden, positions, mask):
        cfg = self.config
        attn = GPTNeoXAttention(cfg, name="attention")(
            nn.LayerNorm(epsilon=cfg.layer_norm_eps, param_dtype=cfg._pdtype, name="input_norm")(hidden),
            positions,
            mask,
        )
        if cfg.use_parallel_residual:
            # x + attn(ln1(x)) + mlp(ln2(x)) — two norms, one residual add.
            mlp = GPTNeoXMLP(cfg, name="mlp")(
                nn.LayerNorm(epsilon=cfg.layer_norm_eps, param_dtype=cfg._pdtype, name="post_attn_norm")(hidden)
            )
            return constrain_activation(hidden + attn + mlp)
        hidden = hidden + attn
        mlp = GPTNeoXMLP(cfg, name="mlp")(
            nn.LayerNorm(epsilon=cfg.layer_norm_eps, param_dtype=cfg._pdtype, name="post_attn_norm")(hidden)
        )
        return constrain_activation(hidden + mlp)


class _ScanBlockBody(nn.Module):
    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, carry, positions, mask):
        return GPTNeoXBlock(self.config, name="block")(carry, positions, mask), None


class GPTNeoXForCausalLM(nn.Module):
    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, positions=None, logits_at=None):
        cfg = self.config
        b, s = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        hidden = constrain_activation(
            nn.Embed(cfg.vocab_size, cfg.hidden_size, param_dtype=cfg._pdtype, name="embed_in")(input_ids)
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
            Block = maybe_remat(GPTNeoXBlock)
            for i in range(cfg.num_hidden_layers):
                hidden = Block(cfg, name=f"layer_{i}")(hidden, positions, attention_mask)
        hidden = nn.LayerNorm(epsilon=cfg.layer_norm_eps, param_dtype=cfg._pdtype, name="final_norm")(
            rows_for_head(hidden, logits_at))
        return nn.Dense(cfg.vocab_size, use_bias=False, param_dtype=cfg._pdtype, name="embed_out")(hidden)


def create_gpt_neox_model(
    config: Optional[GPTNeoXConfig] = None, rng=None, seq_len: int = 2048, param_dtype=None
) -> Model:
    import dataclasses

    config = config or gpt_neox_tiny()
    if param_dtype is not None:
        config = dataclasses.replace(config, param_dtype=str(jnp.dtype(param_dtype)))
    if rng is None:
        rng = jax.random.key(0)
    module = GPTNeoXForCausalLM(config)
    sample = jnp.zeros((1, min(seq_len, config.max_position_embeddings)), dtype=jnp.int32)
    params = jax.jit(module.init)(rng, sample)
    return Model.from_flax(module, params, loss_fn=causal_lm_loss, sharding_rules=GPT_NEOX_SHARDING_RULES)


class GPTNeoXLayeredApply:
    """LayeredApply protocol — the 20B config's route to running inside 16GB of HBM
    via layer streaming (big_modeling.DispatchedModel)."""

    def __init__(self, config: GPTNeoXConfig):
        self.config = config

    def _layer_names(self, params):
        inner = params["params"]
        return sorted((k for k in inner if k.startswith("layer_")), key=lambda s: int(s.split("_")[1]))

    def split(self, params):
        inner = params["params"]
        prelude = {"params": {"embed_in": inner["embed_in"]}}
        if "blocks" in inner:
            stacked = inner["blocks"]["block"]
            layers = [
                {"params": jax.tree_util.tree_map(lambda x: x[i], stacked)}
                for i in range(self.config.num_hidden_layers)
            ]
        else:
            layers = [{"params": inner[name]} for name in self._layer_names(params)]
        tail = {"params": {k: inner[k] for k in ("final_norm", "embed_out") if k in inner}}
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
        hidden = nn.Embed(cfg.vocab_size, cfg.hidden_size).apply(
            {"params": {"embedding": prelude_params["params"]["embed_in"]["embedding"]}}, input_ids
        )
        return (hidden, positions, attention_mask)

    def apply_layer(self, layer_params, carry):
        hidden, positions, mask = carry
        hidden = GPTNeoXBlock(self.config).apply(layer_params, hidden, positions, mask)
        return (hidden, positions, mask)

    def apply_tail(self, tail_params, carry):
        cfg = self.config
        hidden, _, _ = carry
        hidden = nn.LayerNorm(epsilon=cfg.layer_norm_eps).apply(
            {"params": tail_params["params"]["final_norm"]}, hidden
        )
        return nn.Dense(cfg.vocab_size, use_bias=False).apply(
            {"params": tail_params["params"]["embed_out"]}, hidden
        )


def gpt_neox_20b() -> GPTNeoXConfig:
    """EleutherAI GPT-NeoX-20B dims (reference benchmarks/README.md:33)."""
    return GPTNeoXConfig()


def gpt_neox_tiny() -> GPTNeoXConfig:
    return GPTNeoXConfig(
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_hidden_layers=2,
        num_attention_heads=4,
        max_position_embeddings=256,
    )
