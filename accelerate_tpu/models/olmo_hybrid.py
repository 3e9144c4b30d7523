"""Hybrid decoder: gated-delta-rule linear-attention layers with a full
softmax-attention layer every fourth, as Olmo-Hybrid-7B publishes it
(https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json,
`model_type` `olmo_hybrid`; the `linear_*` keys are the gated delta rule's,
Yang et al., arXiv:2412.06464).

A request leaves two kinds of state behind, and the cache collection holds
both:

  - a full-attention layer keeps keys and values a token, in pages, exactly as
    `models/gpt_neox.py` does (`cached_key` / `cached_value`, read by
    `ops.attention.slot_cache_attention`);
  - a linear-attention layer keeps a FIXED state a row whatever the length:
    `recurrent_state` ``[rows, key_dim, heads * value_dim]`` float32 (one
    ``key_dim x value_dim`` matrix a head, the heads' value columns side by
    side: `ops.delta_rule.to_slot_layout`) and `conv_state` ``[rows, taps - 1,
    channels]``, the short convolution's last inputs. A row is a slot of the
    serving engine, or a batch row of `Generator`'s dense cache.

Linear-attention layer (``x`` [T, hidden]): ``[q | k | v] = silu(conv([x W_q |
x W_k | x W_v]))`` (causal, depthwise, `linear_conv_kernel_dim` taps, no
bias); by head ``q <- q / |q| / sqrt(key_dim)``, ``k <- k / |k|``; ``beta =
sigmoid(x W_b)``, doubled when `linear_allow_neg_eigval`; ``alpha = exp(-exp(A_log)
softplus(x W_a + dt_bias))``; the recurrence of `ops/delta_rule.py` — chunked
over a block of tokens, one update for a single token; out ``= W_o
[RMSNorm_head(o) * silu(x W_g)]``. Padded positions of a block (`attention_mask`
0: an insert bucket's tail, a left-padded prompt's head) leave both states as
the last real token left them.

Full-attention layer: heads of ``hidden / heads``, RMSNorm over the whole query
and key projections (the family's QK-norm), causal softmax, NO rotary embedding
(`rope_parameters.rope_theta` is null in the published file: the linear layers
carry order).

Block, both kinds (the family's reordered norm): ``h = x + RMSNorm(mixer(x))``,
``y = h + RMSNorm(SwiGLU(h))``; final RMSNorm, untied head.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..modeling import Model
from ..ops.attention import dot_product_attention, slot_cache_attention, update_decode_cache
from ..ops.delta_rule import (
    CHUNK,
    causal_conv,
    from_slot_layout,
    gated_delta_chunked,
    gated_delta_step,
    l2_normalize,
    to_slot_layout,
)
from ..ops.remat import maybe_remat
from ..parallel.sharding import constrain_activation
from .llama import RMSNorm, ServedConfig, causal_lm_loss, rows_for_head

LINEAR, FULL = "linear_attention", "full_attention"

OLMO_HYBRID_SHARDING_RULES = [
    (r"(wq|wk|wv|wg)/kernel", (None, "model")),
    (r"wo/kernel", ("model", None)),
    (r"mlp/(w_gate|w_up)/kernel", (None, "model")),
    (r"mlp/w_down/kernel", ("model", None)),
    (r"embed_tokens/embedding", ("model", None)),
    (r"lm_head/kernel", (None, "model")),
]


@dataclass
class OlmoHybridConfig(ServedConfig):
    """Keys as the published config names them; defaults are Olmo-Hybrid-7B's."""

    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    # One entry a layer; None = three linear-attention layers then a full one.
    layer_types: Optional[Tuple[str, ...]] = None
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    # Serving, beyond `ServedConfig` (whose page pool holds the full-attention layers): a
    # quantized pool, int8 weights. No `decode_tp_mesh`: by-slot state has no
    # tensor-parallel layout, and the engine's admission says so.
    decode_kv_cache_dtype: str = "bf16"
    weight_dtype: str = "bf16"
    param_dtype: str = "float32"

    def __post_init__(self):
        super().__post_init__()
        if self.layer_types is None:
            self.layer_types = tuple(
                FULL if i % 4 == 3 else LINEAR for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)  # a JSON round trip hands back a list
        if len(self.layer_types) != self.num_hidden_layers or set(self.layer_types) - {LINEAR, FULL}:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, each "
                f"{LINEAR!r} or {FULL!r}; got {self.layer_types!r}"
            )
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("the full-attention layers are multi-head: num_key_value_heads "
                             "must equal num_attention_heads")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError("the linear layers pair one key head with one value head: "
                             "linear_num_key_heads must equal linear_num_value_heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def decode_cache_kv_heads(self) -> int:
        """Heads a cache of keys and values stores: the model's, rounded up to
        whole 16-row tiles of bfloat16 (30 -> 32; the two extra are zeros that
        attend to nothing and are dropped). A pool ``[pages, 16, 30, 128]``
        bfloat16 lives on a TPU page-size-minor, a decode chunk's loop wants it
        head-minor (which pads 30 to 32 anyway), and every chunk then copies
        all eight pools in and out (seen in the compile for a described v5e:
        3.84 GB of temporaries at this model's sizes). The padding is made
        here, where it is counted."""
        return -(-self.num_attention_heads // 16) * 16

    @property
    def linear_conv_channels(self) -> int:
        """Channels the short convolution runs over: ``[q | k | v]``."""
        return self.linear_num_key_heads * (2 * self.linear_key_head_dim + self.linear_value_head_dim)

    @property
    def decode_scan_chunk(self) -> int:
        """Tokens a chunk of the recurrence's prefill form (`serve.insert.scan_chunks`)."""
        return CHUNK

    def insert_span_counts(self, bucket: int, suffix_tokens: int, matched_len: int, window: int) -> dict:
        """`scan_chunks`: the chunks the bucket is for a layer's chunked recurrence, pads included."""
        return {"scan_chunks": -(-bucket // self.decode_scan_chunk)} if LINEAR in self.layer_types else {}

    @property
    def _pdtype(self):
        return jnp.dtype(self.param_dtype)


def _dense(features: int, cfg: OlmoHybridConfig, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, param_dtype=cfg._pdtype, name=name)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log(U(1, 16))``: decays from slow to fast heads (the paper's initialisation)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of ``exp(U(log 0.001, log 0.1))``."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(0.001), jnp.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class GatedDeltaNet(nn.Module):
    """The linear-attention mixer. `mask` is a ``[B, T]`` mark of real
    positions (None: all real) — except in slot decode, where it is the page
    table, which this layer has no use for."""

    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, hidden, mask):
        cfg = self.config
        b, t, _ = hidden.shape
        heads, dk, dv = cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
        taps, channels = cfg.linear_conv_kernel_dim, cfg.linear_conv_channels
        cached = bool(cfg.decode_cache_length)
        valid = None if cfg.decode_slot_cache or mask is None else mask.astype(bool)
        if cfg.decode_slot_cache and t != 1:
            raise ValueError(
                f"a block of {t} tokens against the slot cache: a verify block would advance the "
                "recurrent state past tokens it may reject, and no roll-back is built")

        qkv = jnp.concatenate(
            [_dense(heads * dk, cfg, "wq")(hidden), _dense(heads * dk, cfg, "wk")(hidden),
             _dense(heads * dv, cfg, "wv")(hidden)], axis=-1)
        conv_weight = self.param(
            "conv_weight", nn.initializers.normal(0.02), (taps, channels), cfg._pdtype)
        conv_var = state_var = None
        if cached:
            conv_var = self.variable("cache", "conv_state", jnp.zeros, (b, taps - 1, channels), hidden.dtype)
            state_var = self.variable(
                "cache", "recurrent_state", jnp.zeros, (b, dk, heads * dv), jnp.float32)
        with jax.named_scope("delta_conv"):
            conv_in = conv_var.value if cached else jnp.zeros((b, taps - 1, channels), hidden.dtype)
            qkv, conv_out = causal_conv(qkv, conv_weight, conv_in, valid)
            qkv = nn.silu(qkv)
        q, k, v = jnp.split(qkv, [heads * dk, 2 * heads * dk], axis=-1)
        q = l2_normalize(q.reshape(b, t, heads, dk)) * (dk ** -0.5)
        k = l2_normalize(k.reshape(b, t, heads, dk))
        v = v.reshape(b, t, heads, dv)

        beta = nn.sigmoid(_dense(heads, cfg, "wb")(hidden).astype(jnp.float32))
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
        a_log = self.param("A_log", _a_log_init, (heads,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (heads,), jnp.float32)
        log_alpha = -jnp.exp(a_log) * nn.softplus(
            _dense(heads, cfg, "wa")(hidden).astype(jnp.float32) + dt_bias)
        if valid is not None:  # a padded position: alpha = 1, beta = 0
            beta = jnp.where(valid[..., None], beta, 0.0)
            log_alpha = jnp.where(valid[..., None], log_alpha, 0.0)

        if cached and t == 1:
            with jax.named_scope("delta_step"):
                o, state = gated_delta_step(
                    q[:, 0], k[:, 0], v[:, 0], jnp.exp(log_alpha[:, 0]), beta[:, 0], state_var.value)
                o = o[:, None]
        else:
            with jax.named_scope("delta_scan"):
                before = (from_slot_layout(state_var.value, heads) if cached
                          else jnp.zeros((b, heads, dk, dv), jnp.float32))
                o, state = gated_delta_chunked(q, k, v, log_alpha, beta, before)
                state = to_slot_layout(state)
        if cached:
            conv_var.value, state_var.value = conv_out, state

        with jax.named_scope("delta_gate_norm"):
            o = RMSNorm(cfg.rms_norm_eps, name="out_norm")(o).astype(hidden.dtype)
            gate = nn.silu(_dense(heads * dv, cfg, "wg")(hidden))
            o = o.reshape(b, t, heads * dv) * gate
        return _dense(cfg.hidden_size, cfg, "wo")(o)


class FullAttention(nn.Module):
    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, hidden, positions, mask):
        cfg = self.config
        b, s, _ = hidden.shape
        h, d = cfg.num_attention_heads, cfg.head_dim
        q = RMSNorm(cfg.rms_norm_eps, name="q_norm")(_dense(h * d, cfg, "wq")(hidden)).reshape(b, s, h, d)
        k = RMSNorm(cfg.rms_norm_eps, name="k_norm")(_dense(h * d, cfg, "wk")(hidden)).reshape(b, s, h, d)
        v = _dense(h * d, cfg, "wv")(hidden).reshape(b, s, h, d)
        if cfg.decode_cache_length:
            L = cfg.decode_cache_length
            extra = ((0, 0), (0, 0), (0, cfg.decode_cache_kv_heads - h), (0, 0))
            q, k, v = (jnp.pad(x, extra) for x in (q, k, v))  # whole tiles of heads in the cache
            if cfg.decode_slot_cache:
                out = slot_cache_attention(
                    self, q, k, v, L, positions,
                    page_table=mask,
                    page_size=cfg.decode_page_size,
                    num_pages=cfg.decode_num_pages,
                    attention_impl=cfg.decode_attention_impl,
                    kv_cache_dtype=cfg.decode_kv_cache_dtype,
                )
            else:
                k_all, v_all, decode_mask = update_decode_cache(self, k, v, L, pad_mask=mask)
                out = dot_product_attention(q, k_all, v_all, mask=decode_mask, causal=False)
            out = out[:, :, :h]
        else:
            out = dot_product_attention(q, k, v, mask=mask, causal=True)
        return _dense(cfg.hidden_size, cfg, "wo")(out.reshape(b, s, h * d))


class SwiGLU(nn.Module):
    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, hidden):
        cfg = self.config
        gate = _dense(cfg.intermediate_size, cfg, "w_gate")(hidden)
        up = _dense(cfg.intermediate_size, cfg, "w_up")(hidden)
        return _dense(cfg.hidden_size, cfg, "w_down")(nn.silu(gate) * up)


class OlmoHybridLayer(nn.Module):
    config: OlmoHybridConfig
    kind: str

    @nn.compact
    def __call__(self, hidden, positions, mask):
        cfg = self.config
        if self.kind == LINEAR:
            mixed = GatedDeltaNet(cfg, name="mixer")(hidden, mask)
        else:
            mixed = FullAttention(cfg, name="mixer")(hidden, positions, mask)
        hidden = constrain_activation(hidden + RMSNorm(cfg.rms_norm_eps, name="post_mixer_norm")(mixed))
        ffn = SwiGLU(cfg, name="mlp")(hidden)
        return constrain_activation(hidden + RMSNorm(cfg.rms_norm_eps, name="post_mlp_norm")(ffn))


class OlmoHybridForCausalLM(nn.Module):
    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, positions=None, logits_at=None):
        cfg = self.config
        b, s = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        hidden = constrain_activation(
            nn.Embed(cfg.vocab_size, cfg.hidden_size, param_dtype=cfg._pdtype, name="embed_tokens")(input_ids)
        )
        Layer = maybe_remat(OlmoHybridLayer)
        for i, kind in enumerate(cfg.layer_types):
            hidden = Layer(cfg, kind, name=f"layer_{i}")(hidden, positions, attention_mask)
        hidden = RMSNorm(cfg.rms_norm_eps, name="final_norm")(rows_for_head(hidden, logits_at))
        return _dense(cfg.vocab_size, cfg, "lm_head")(hidden)


def create_olmo_hybrid_model(
    config: Optional[OlmoHybridConfig] = None, rng=None, seq_len: int = 2048, param_dtype=None
) -> Model:
    config = config or olmo_hybrid_tiny()
    if param_dtype is not None:
        config = dataclasses.replace(config, param_dtype=str(jnp.dtype(param_dtype)))
    if rng is None:
        rng = jax.random.key(0)
    module = OlmoHybridForCausalLM(config)
    sample = jnp.zeros((1, min(seq_len, config.max_position_embeddings, 128)), dtype=jnp.int32)
    params = jax.jit(module.init)(rng, sample)
    return Model.from_flax(module, params, loss_fn=causal_lm_loss, sharding_rules=OLMO_HYBRID_SHARDING_RULES)


def olmo_hybrid_7b() -> OlmoHybridConfig:
    """Olmo-Hybrid-7B as published: 32 layers, 7.43 B parameters (14.9 GB in
    bfloat16 — more than one v5e chip holds beside any cache)."""
    return OlmoHybridConfig()


def olmo_hybrid_tiny() -> OlmoHybridConfig:
    """Two periods of the layer pattern at small widths, for tests."""
    return OlmoHybridConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256, num_hidden_layers=8,
        num_attention_heads=4, num_key_value_heads=4, max_position_embeddings=256,
        linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=32,
    )
