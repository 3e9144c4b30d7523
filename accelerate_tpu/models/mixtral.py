"""Mixtral-family sparse-MoE decoder: Llama backbone with a top-k expert-parallel FFN.

The MoE model family the reference can only reach through DeepSpeed-MoE leaf modules
(dataclasses.py:992-1010); here it's in-tree with first-class expert-axis sharding
(parallel/expert.py). The backbone (RMSNorm, RoPE, GQA attention) is shared with
models/llama.py.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..modeling import Model
from ..parallel.expert import EXPERT_SHARDING_RULES, MoEBlock
from ..ops.remat import maybe_remat
from .llama import LlamaAttention, LlamaConfig, RMSNorm, rows_for_head

MIXTRAL_SHARDING_RULES = [
    (r"(wq|wk|wv)/kernel", (None, "model")),
    (r"wo/kernel", ("model", None)),
    (r"embed_tokens/embedding", ("model", None)),
    (r"lm_head/kernel", (None, "model")),
    (r"router/kernel", ()),  # tiny; replicate
] + EXPERT_SHARDING_RULES


@dataclass
class MixtralConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_local_experts: int = 8
    num_experts_per_tok: int = 2
    capacity_factor: float = 1.25
    max_position_embeddings: int = 32768
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-5
    router_aux_loss_coef: float = 0.02
    router_z_loss_coef: float = 0.001
    # Serving: >0 routes the shared LlamaAttention through the KV-cache path
    # (Generator sets it via dataclasses.replace, same as every causal family).
    decode_cache_length: int = 0

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def as_llama(self) -> LlamaConfig:
        """Attention-relevant view for the shared backbone modules."""
        return LlamaConfig(
            vocab_size=self.vocab_size,
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_hidden_layers=self.num_hidden_layers,
            num_attention_heads=self.num_attention_heads,
            num_key_value_heads=self.num_key_value_heads,
            max_position_embeddings=self.max_position_embeddings,
            rope_theta=self.rope_theta,
            rms_norm_eps=self.rms_norm_eps,
            decode_cache_length=self.decode_cache_length,
        )


class MixtralLayer(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, hidden, positions, mask):
        cfg = self.config
        attn = LlamaAttention(cfg.as_llama(), name="attention")(
            RMSNorm(cfg.rms_norm_eps, name="input_norm")(hidden), positions, mask
        )
        hidden = hidden + attn
        moe_out, aux = MoEBlock(
            hidden_size=cfg.hidden_size,
            intermediate_size=cfg.intermediate_size,
            num_experts=cfg.num_local_experts,
            top_k=cfg.num_experts_per_tok,
            capacity_factor=cfg.capacity_factor,
            name="moe",
        )(RMSNorm(cfg.rms_norm_eps, name="post_attn_norm")(hidden))
        return hidden + moe_out, aux


class MixtralForCausalLM(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, positions=None, return_aux: bool = False,
                 logits_at=None):
        cfg = self.config
        b, s = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        hidden = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens")(input_ids)
        total_aux = {"load_balance_loss": jnp.float32(0.0), "router_z_loss": jnp.float32(0.0)}
        Layer = maybe_remat(MixtralLayer)
        for i in range(cfg.num_hidden_layers):
            hidden, aux = Layer(cfg, name=f"layer_{i}")(hidden, positions, attention_mask)
            total_aux = {k: total_aux[k] + aux[k] for k in total_aux}
        hidden = RMSNorm(cfg.rms_norm_eps, name="final_norm")(rows_for_head(hidden, logits_at))
        logits = nn.Dense(cfg.vocab_size, use_bias=False, name="lm_head")(hidden)
        if return_aux:
            n = jnp.float32(max(cfg.num_hidden_layers, 1))
            return logits, {k: v / n for k, v in total_aux.items()}
        return logits


def make_moe_causal_lm_loss(config: "MixtralConfig"):
    """Next-token cross-entropy + router load-balance/z losses (the Mixtral objective)."""

    def moe_causal_lm_loss(params, batch, apply_fn):
        logits, aux = apply_fn(
            params, batch["input_ids"], batch.get("attention_mask"), return_aux=True
        )
        labels = batch.get("labels", batch["input_ids"])
        shift_logits = logits[:, :-1].astype(jnp.float32)
        shift_labels = labels[:, 1:]
        logp = jax.nn.log_softmax(shift_logits, axis=-1)
        valid = (shift_labels >= 0).astype(jnp.float32)
        safe_labels = jnp.maximum(shift_labels, 0)
        nll = -jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
        ce = (nll * valid).sum() / jnp.maximum(valid.sum(), 1.0)
        total = (
            ce
            + config.router_aux_loss_coef * aux["load_balance_loss"]
            + config.router_z_loss_coef * aux["router_z_loss"]
        )
        return total, {"ce": ce, **aux}

    return moe_causal_lm_loss


def create_mixtral_model(config: Optional[MixtralConfig] = None, rng=None, seq_len: int = 2048) -> Model:
    config = config or mixtral_tiny()
    if rng is None:
        rng = jax.random.key(0)
    module = MixtralForCausalLM(config)
    sample = jnp.zeros((1, min(seq_len, config.max_position_embeddings)), dtype=jnp.int32)
    params = module.init(rng, sample)
    return Model.from_flax(
        module,
        params,
        loss_fn=make_moe_causal_lm_loss(config),
        sharding_rules=MIXTRAL_SHARDING_RULES,
    )


def mixtral_8x7b() -> MixtralConfig:
    return MixtralConfig()


def mixtral_tiny() -> MixtralConfig:
    """Test-size config."""
    return MixtralConfig(
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_local_experts=4,
        num_experts_per_tok=2,
        max_position_embeddings=256,
        rope_theta=10000.0,
    )
