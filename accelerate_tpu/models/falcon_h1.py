"""Parallel hybrid decoder: every block runs a Mamba-2 state-space mixer AND
grouped-query softmax attention side by side on one normed input, as
Falcon-H1-34B-Instruct publishes it
(https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json,
`model_type` `falcon_h1`; the `mamba_*` keys are the state-space mixer's, Dao &
Gu, arXiv:2405.21060), under the family's muP multipliers at their published
places.

A request leaves two kinds of state behind IN EVERY LAYER, and a layer's cache
holds both:

  - attention keeps keys and values a token, in pages, exactly as
    `models/llama.py` does (`cached_key` / `cached_value`, `num_key_value_heads`
    heads of `head_dim`, read by `ops.attention.slot_cache_attention`);
  - the mixer keeps a FIXED state a row whatever the length: `recurrent_state`
    ``[rows, mamba_d_state, mamba_n_heads * mamba_d_head]`` float32 (one ``d_head
    x d_state`` matrix a head, stored transposed with the heads' columns side by
    side: `ops.ssm.to_slot_layout`) and `conv_state` ``[rows, mamba_d_conv - 1,
    channels]``, the short convolution's last inputs. A row is a slot of the
    serving engine, or a batch row of `Generator`'s dense cache.

Block (``h`` [T, hidden]): ``u = RMSNorm(h)``; ``h <- h + ssm_out_multiplier *
Mixer(ssm_in_multiplier * u) + attention_out_multiplier *
Attn(attention_in_multiplier * u)``; ``h <- h + MLP(RMSNorm(h))``.

Mixer: ``[z | x | B | C | dt] = (u W_in) * m``, ``m`` the five `ssm_multipliers`
spread over the segments; ``[x | B | C] <- silu(conv([x | B | C]) + b)`` (causal,
depthwise, `mamba_d_conv` taps); ``dt <- softplus(dt + dt_bias)``, ``A =
-exp(A_log)``; the recurrence of `ops/ssm.py` — chunked over a block of tokens,
one update for a single token — heads ``0 .. H/G - 1`` on group 0's ``B``,
``C``; ``y <- y + D x``; ``o = RMSNorm_group(y * silu(z))`` over each group's
channels (`mamba_norm_before_gate` false); out ``= o W_out``. Padded positions
of a block (`attention_mask` 0: an insert bucket's tail, a left-padded prompt's
head) take ``dt = 0`` and leave both states as the last real token left them.

Attention: ``k <- k * key_multiplier``; rotary embedding over the whole head,
half-split pairs; causal softmax of ``q k^T / sqrt(head_dim)``.

MLP: ``W_down(silu(W_gate(v) * mlp_multipliers[0]) * W_up(v)) * mlp_multipliers[1]``.

Embedding ``E[ids] * embedding_multiplier``; final RMSNorm; ``logits = (h
W_head) * lm_head_multiplier``, untied.
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
from ..ops.delta_rule import causal_conv
from ..ops.remat import maybe_remat
from ..ops.ssm import from_slot_layout, ssd_chunked, ssm_step, to_slot_layout
from ..parallel.sharding import constrain_activation
from .llama import RMSNorm, ServedConfig, causal_lm_loss, rotary_embedding, rows_for_head
from .olmo_hybrid import _a_log_init, _dt_bias_init

FALCON_H1_SHARDING_RULES = [
    (r"attention/(wq|wk|wv)/kernel", (None, "model")),
    (r"attention/wo/kernel", ("model", None)),
    (r"mixer/w_in/kernel", (None, "model")),
    (r"mixer/w_out/kernel", ("model", None)),
    (r"mlp/(w_gate|w_up)/kernel", (None, "model")),
    (r"mlp/w_down/kernel", ("model", None)),
    (r"embed_tokens/embedding", ("model", None)),
    (r"lm_head/kernel", (None, "model")),
]


@dataclass
class FalconH1Config(ServedConfig):
    """Keys as the published config names them; defaults are Falcon-H1-34B-Instruct's."""

    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 262144
    rope_theta: float = 1e11
    rms_norm_eps: float = 1e-5
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_n_groups: int = 2
    mamba_d_state: int = 256
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    # the muP multipliers, each applied where the module docstring puts it
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: Tuple[float, ...] = (0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738)
    mlp_multipliers: Tuple[float, ...] = (0.1767766952966369, 0.011160714285714284)
    # Serving, beyond `ServedConfig` (whose page pool holds the attention half of every layer): a
    # quantized pool, int8 weights. No `decode_tp_mesh`: by-slot state has no
    # tensor-parallel layout, and the engine's admission says so.
    decode_kv_cache_dtype: str = "bf16"
    weight_dtype: str = "bf16"
    param_dtype: str = "float32"

    def __post_init__(self):
        super().__post_init__()
        # a JSON round trip hands back lists
        self.ssm_multipliers = tuple(float(m) for m in self.ssm_multipliers)
        self.mlp_multipliers = tuple(float(m) for m in self.mlp_multipliers)
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers scales the five segments [z | x | B | C | dt] and "
                             "mlp_multipliers the gate and the down projection")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError("mamba_d_ssm must be mamba_n_heads x mamba_d_head")
        if self.mamba_n_heads % self.mamba_n_groups or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads share B and C (keys and values) by whole groups: mamba_n_heads must be a "
                             "multiple of mamba_n_groups and num_attention_heads of num_key_value_heads")

    @property
    def conv_channels(self) -> int:
        """Channels the short convolution runs over: ``[x | B | C]``."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_segments(self) -> Tuple[int, ...]:
        """Widths of ``W_in``'s output, in order: ``[z | x | B | C | dt]``."""
        bc = self.mamba_n_groups * self.mamba_d_state
        return (self.mamba_d_ssm, self.mamba_d_ssm, bc, bc, self.mamba_n_heads)

    @property
    def decode_scan_chunk(self) -> int:
        """Tokens a chunk of the recurrence's prefill form (`serve.insert.scan_chunks`)."""
        return self.mamba_chunk_size

    def insert_span_counts(self, bucket: int, suffix_tokens: int, matched_len: int, window: int) -> dict:
        """`scan_chunks`: the chunks the bucket is for a layer's chunked recurrence, pads included."""
        return {"scan_chunks": -(-bucket // self.decode_scan_chunk)}

    @property
    def _pdtype(self):
        return jnp.dtype(self.param_dtype)


def _dense(features: int, cfg: FalconH1Config, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, param_dtype=cfg._pdtype, name=name)


class Mamba2Mixer(nn.Module):
    """The state-space mixer. `mask` is a ``[B, T]`` mark of real positions
    (None: all real) — except in slot decode, where it is the page table, which
    this half of the layer has no use for."""

    config: FalconH1Config

    @nn.compact
    def __call__(self, hidden, mask):
        cfg = self.config
        b, t, _ = hidden.shape
        heads, p, groups, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups, cfg.mamba_d_state
        taps, channels, d_ssm = cfg.mamba_d_conv, cfg.conv_channels, cfg.mamba_d_ssm
        cached = bool(cfg.decode_cache_length)
        valid = None if cfg.decode_slot_cache or mask is None else mask.astype(bool)
        if cfg.decode_slot_cache and t != 1:
            raise ValueError(
                f"a block of {t} tokens against the slot cache: a verify block would advance the "
                "recurrent state past tokens it may reject, and no roll-back is built")

        with jax.named_scope("ssm_in_proj"):
            scale = jnp.concatenate([jnp.full((width,), m, jnp.float32)
                                     for width, m in zip(cfg.in_proj_segments, cfg.ssm_multipliers)])
            projected = _dense(sum(cfg.in_proj_segments), cfg, "w_in")(hidden) * scale.astype(hidden.dtype)
            z, xbc, dt = jnp.split(projected, [d_ssm, d_ssm + channels], axis=-1)
        conv_weight = self.param("conv_weight", nn.initializers.normal(0.02), (taps, channels), cfg._pdtype)
        conv_bias = self.param("conv_bias", nn.initializers.zeros, (channels,), cfg._pdtype)
        conv_var = state_var = None
        if cached:
            conv_var = self.variable("cache", "conv_state", jnp.zeros, (b, taps - 1, channels), hidden.dtype)
            state_var = self.variable("cache", "recurrent_state", jnp.zeros, (b, n, heads * p), jnp.float32)
        with jax.named_scope("ssm_conv"):
            conv_in = conv_var.value if cached else jnp.zeros((b, taps - 1, channels), hidden.dtype)
            xbc, conv_out = causal_conv(xbc, conv_weight, conv_in, valid)
            xbc = nn.silu(xbc + conv_bias.astype(xbc.dtype))
        x, b_in, c_in = jnp.split(xbc, [d_ssm, d_ssm + groups * n], axis=-1)
        x = x.reshape(b, t, heads, p)
        b_in, c_in = b_in.reshape(b, t, groups, n), c_in.reshape(b, t, groups, n)

        a = -jnp.exp(self.param("A_log", _a_log_init, (heads,), jnp.float32))
        dt_bias = self.param("dt_bias", _dt_bias_init, (heads,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
        dt = nn.softplus(dt.astype(jnp.float32) + dt_bias)
        if valid is not None:  # a padded position: decay 1, no input
            dt = jnp.where(valid[..., None], dt, 0.0)

        if cached and t == 1:
            with jax.named_scope("ssm_step"):
                y, state = ssm_step(x[:, 0], dt[:, 0], a, b_in[:, 0], c_in[:, 0], state_var.value)
                y = y[:, None]
        else:
            with jax.named_scope("ssm_scan"):
                before = (from_slot_layout(state_var.value, heads) if cached
                          else jnp.zeros((b, heads, p, n), jnp.float32))
                y, state = ssd_chunked(x, dt, a, b_in, c_in, before, chunk=cfg.mamba_chunk_size)
                state = to_slot_layout(state)
        if cached:
            conv_var.value, state_var.value = conv_out, state

        with jax.named_scope("ssm_gate_norm"):
            y = y + skip[:, None] * x.astype(jnp.float32)
            gated = (y.reshape(b, t, d_ssm) * nn.silu(z.astype(jnp.float32))).reshape(b, t, groups, d_ssm // groups)
            normed = gated * jax.lax.rsqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + cfg.rms_norm_eps)
            norm_scale = self.param("norm_scale", nn.initializers.ones, (d_ssm,), cfg._pdtype)
            o = (normed.reshape(b, t, d_ssm) * norm_scale).astype(hidden.dtype)
        return _dense(cfg.hidden_size, cfg, "w_out")(o)


class GroupedQueryAttention(nn.Module):
    config: FalconH1Config

    @nn.compact
    def __call__(self, hidden, positions, mask):
        cfg = self.config
        b, s, _ = hidden.shape
        hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        q = _dense(hq * d, cfg, "wq")(hidden).reshape(b, s, hq, d)
        k = (_dense(hkv * d, cfg, "wk")(hidden) * jnp.asarray(cfg.key_multiplier, hidden.dtype)).reshape(b, s, hkv, d)
        v = _dense(hkv * d, cfg, "wv")(hidden).reshape(b, s, hkv, d)
        q = rotary_embedding(q, positions, cfg.rope_theta)
        k = rotary_embedding(k, positions, cfg.rope_theta)
        if cfg.decode_cache_length:
            if cfg.decode_slot_cache:
                out = slot_cache_attention(
                    self, q, k, v, cfg.decode_cache_length, positions,
                    page_table=mask,
                    page_size=cfg.decode_page_size,
                    num_pages=cfg.decode_num_pages,
                    attention_impl=cfg.decode_attention_impl,
                    kv_cache_dtype=cfg.decode_kv_cache_dtype,
                )
            else:
                k_all, v_all, decode_mask = update_decode_cache(self, k, v, cfg.decode_cache_length, pad_mask=mask)
                out = dot_product_attention(q, k_all, v_all, mask=decode_mask, causal=False)
        else:
            out = dot_product_attention(q, k, v, mask=mask, causal=True)
        return _dense(cfg.hidden_size, cfg, "wo")(out.reshape(b, s, hq * d))


class ScaledSwiGLU(nn.Module):
    config: FalconH1Config

    @nn.compact
    def __call__(self, hidden):
        cfg = self.config
        gate_m, down_m = (jnp.asarray(m, hidden.dtype) for m in cfg.mlp_multipliers)
        gate = _dense(cfg.intermediate_size, cfg, "w_gate")(hidden) * gate_m
        up = _dense(cfg.intermediate_size, cfg, "w_up")(hidden)
        return _dense(cfg.hidden_size, cfg, "w_down")(nn.silu(gate) * up) * down_m


class FalconH1Layer(nn.Module):
    config: FalconH1Config

    @nn.compact
    def __call__(self, hidden, positions, mask):
        cfg = self.config
        scaled = lambda x, m: x * jnp.asarray(m, x.dtype)  # noqa: E731
        normed = RMSNorm(cfg.rms_norm_eps, name="input_norm")(hidden)
        mixed = Mamba2Mixer(cfg, name="mixer")(scaled(normed, cfg.ssm_in_multiplier), mask)
        attended = GroupedQueryAttention(cfg, name="attention")(
            scaled(normed, cfg.attention_in_multiplier), positions, mask)
        hidden = constrain_activation(
            hidden + scaled(mixed, cfg.ssm_out_multiplier) + scaled(attended, cfg.attention_out_multiplier))
        ffn = ScaledSwiGLU(cfg, name="mlp")(RMSNorm(cfg.rms_norm_eps, name="pre_mlp_norm")(hidden))
        return constrain_activation(hidden + ffn)


class FalconH1ForCausalLM(nn.Module):
    config: FalconH1Config

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, positions=None, logits_at=None):
        cfg = self.config
        b, s = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        hidden = nn.Embed(cfg.vocab_size, cfg.hidden_size, param_dtype=cfg._pdtype, name="embed_tokens")(input_ids)
        hidden = constrain_activation(hidden * jnp.asarray(cfg.embedding_multiplier, hidden.dtype))
        Layer = maybe_remat(FalconH1Layer)
        for i in range(cfg.num_hidden_layers):
            hidden = Layer(cfg, name=f"layer_{i}")(hidden, positions, attention_mask)
        hidden = RMSNorm(cfg.rms_norm_eps, name="final_norm")(rows_for_head(hidden, logits_at))
        logits = _dense(cfg.vocab_size, cfg, "lm_head")(hidden)
        return logits * jnp.asarray(cfg.lm_head_multiplier, logits.dtype)


def create_falcon_h1_model(
    config: Optional[FalconH1Config] = None, rng=None, seq_len: int = 2048, param_dtype=None
) -> Model:
    config = config or falcon_h1_tiny()
    if param_dtype is not None:
        config = dataclasses.replace(config, param_dtype=str(jnp.dtype(param_dtype)))
    if rng is None:
        rng = jax.random.key(0)
    module = FalconH1ForCausalLM(config)
    sample = jnp.zeros((1, min(seq_len, config.max_position_embeddings, 128)), dtype=jnp.int32)
    params = jax.jit(module.init)(rng, sample)
    return Model.from_flax(module, params, loss_fn=causal_lm_loss, sharding_rules=FALCON_H1_SHARDING_RULES)


def falcon_h1_34b() -> FalconH1Config:
    """Falcon-H1-34B-Instruct as published: 72 blocks, 33.64 B parameters (67.3
    GB in bfloat16 — a chip holds 6 blocks beside the embedding and the head)."""
    return FalconH1Config()


def falcon_h1_tiny() -> FalconH1Config:
    """Three blocks at small widths, for tests: 4 query heads over 2 KV heads, 4
    mixer heads in 2 groups, every multiplier away from 1."""
    return FalconH1Config(
        vocab_size=512, hidden_size=128, intermediate_size=256, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, head_dim=32, max_position_embeddings=256,
        rope_theta=10000.0, mamba_d_ssm=128, mamba_n_heads=4, mamba_d_head=32, mamba_n_groups=2,
        mamba_d_state=16, mamba_chunk_size=16, attention_in_multiplier=0.8,
    )
