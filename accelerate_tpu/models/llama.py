"""Llama-family decoder in flax — the flagship FSDP model (BASELINE.json:
"Llama-3-8B full-shard fine-tune on TPU mesh" / big_model_inference Llama-70B).

Fresh flax implementation: RMSNorm (fp32 accumulation), rotary embeddings, grouped-query
attention through the shared attention seam, SwiGLU MLP, optional `lax.scan` over layers
(one compiled layer body — faster compiles for deep stacks), and Megatron-layout TP
rules + FSDP-friendly shapes.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import numpy as np

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

# The hand-written Megatron layout. Since the sharding planner landed
# (parallel/planner.py, sharding_rules="auto") this table is the parity
# ORACLE the planner is tested against, not the required source — the auto
# plan must match or beat it on modeled cost with identical greedy tokens.
LLAMA_SHARDING_RULES = [
    (r"(wq|wk|wv)/kernel", (None, "model")),
    (r"wo/kernel", ("model", None)),
    (r"(w_gate|w_up)/kernel", (None, "model")),
    (r"w_down/kernel", ("model", None)),
    (r"embed_tokens/embedding", ("model", None)),
    (r"lm_head/kernel", (None, "model")),
]


@dataclass
class ServedConfig:
    """What every family that `serving.ContinuousBatcher` serves declares: the
    five fields the engine sets on the prefill and the decode module's config,
    their one check, and what the family's layers add to a span's counts.

    What a family supports beyond that is what its own config carries — the
    engine asks once (`serving._family_facts`) and refuses by name what is
    missing: `weight_dtype` (int8 weights), `decode_kv_cache_dtype` (a
    quantized page pool), `decode_tp_mesh` (a tensor-parallel engine), and a
    `decode_kv_row_values` property for a cache of latent rows."""

    # When set, attention keeps a [B, decode_cache_length] KV cache in the flax
    # "cache" collection (incremental decoding); 0 = normal training/forward path.
    decode_cache_length: int = 0
    # Slot-batched serving: every batch row is an independent request slot whose
    # decode position comes from the `positions` argument (per-row scatter
    # writes) instead of the shared `cache_index`.
    decode_slot_cache: bool = False
    # The slot cache's pool: K/V live in decode_num_pages fixed-size pages
    # ([num_pages, page_size, h, d]), and the per-slot page tables ride in
    # through the `attention_mask` argument as [B, pages_per_slot] int32 traced
    # operands (slot decode never carries a boolean mask, so the seam is free).
    decode_page_size: int = 0
    decode_num_pages: int = 0
    # The decode read: "xla" = the loop over blocks of live pages (the parity
    # oracle); "pallas_paged" = the ops/paged_attention kernels, which walk the
    # page table inside the kernel. Resolved by the engine (`attention_impl=`).
    decode_attention_impl: str = "xla"

    def __post_init__(self):
        if self.decode_slot_cache and self.decode_page_size < 1:
            raise ValueError(
                "decode_slot_cache=True needs decode_page_size >= 1: the slot "
                "cache is a page pool"
            )

    def insert_span_counts(self, bucket: int, suffix_tokens: int, matched_len: int, window: int) -> dict:
        """What this family's layers add to a `serve.insert` span: counts of the
        work an insert of `bucket` rows (`suffix_tokens` of them real) does behind
        `matched_len` cached positions, in a slot whose padded window is `window`."""
        return {}

    def chunk_span_counts(self, rows: int) -> dict:
        """What this family's layers add to a `serve.decode_chunk` span, from the
        chunk's `rows`: busy slots times steps."""
        return {}


@dataclass
class LlamaConfig(ServedConfig):
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 8192
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    scan_layers: bool = False
    # KV page-pool storage dtype: "bf16" keeps the
    # model compute dtype; "int8"/"fp8_e4m3" store pages quantized with
    # per-page-per-head scale pools riding in the cache collection
    # (ops/quantization.py). Threaded from ContinuousBatcher(kv_cache_dtype=).
    decode_kv_cache_dtype: str = "bf16"
    # Weight storage dtype for the serving programs: "int8" runs every Dense
    # whose kernel is a quantized entry (quantize_params_int8) through the
    # fused int8-epilogue matmul via the weight_autocast interceptor.
    weight_dtype: str = "bf16"
    # Tensor-parallel decode submesh (serving.ContinuousBatcher(tp=N)): the
    # 1-axis ("model",) jax Mesh the engine's sharded executables span. The
    # XLA paths need nothing (GSPMD partitions them off the operand
    # shardings); the Pallas page-walk kernels read this to shard_map over
    # the KV-head grid, since pallas_call has no GSPMD partitioning rule.
    # None = single-device serving, byte-for-byte the pre-TP behavior.
    decode_tp_mesh: Optional[Any] = None

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def rotary_embedding(x, positions, theta: float, inv_freq=None):
    """Apply RoPE to [B, S, H, D] given [B, S] positions. `inv_freq` [D/2]
    gives the pairs' frequencies where they are not `theta`'s own (a scaled
    RoPE such as YaRN: `models/latent_moe.yarn_inv_freq`)."""
    d = x.shape[-1]
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    else:
        inv_freq = jnp.asarray(inv_freq, jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(x.dtype)


def rows_for_head(hidden, logits_at):
    """The rows of `hidden` `[B, S, ...]` a causal LM's tail — the final norm, the
    head, whatever a family has around them — is applied to. `logits_at` None:
    every row (training, a decode step and a verify block read them all). A `[B]`
    int32 index into `S`: that one row of each entry, `[B, 1, ...]`, taken BEFORE
    the tail, so a program that samples one token (an insert at its prompt's
    last real row, `generate()`'s prefill at its last column) never computes
    `[B, S, V]`. An index outside `S` is clamped, as `dynamic_slice` clamps."""
    if logits_at is None:
        return hidden
    index = jnp.asarray(logits_at, jnp.int32).reshape(hidden.shape[0], 1, 1)
    return jnp.take_along_axis(hidden, index, axis=1, mode="clip")


class LlamaAttention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, hidden, positions, mask):
        cfg = self.config
        b, s, _ = hidden.shape
        hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        q = nn.Dense(hq * d, use_bias=False, name="wq")(hidden).reshape(b, s, hq, d)
        k = nn.Dense(hkv * d, use_bias=False, name="wk")(hidden).reshape(b, s, hkv, d)
        v = nn.Dense(hkv * d, use_bias=False, name="wv")(hidden).reshape(b, s, hkv, d)
        q = rotary_embedding(q, positions, cfg.rope_theta)
        k = rotary_embedding(k, positions, cfg.rope_theta)

        if cfg.decode_cache_length:
            if cfg.decode_slot_cache:
                # Continuous-batching decode: each slot row writes at its OWN
                # position (per-row scatter) and attends its written prefix
                # only. `mask` is the slot page table ([B, pages_per_slot]
                # int32) mapping positions onto pool pages;
                # decode_attention_impl picks the XLA live-page read or the
                # fused Pallas page-walk kernels.
                out = slot_cache_attention(
                    self, q, k, v, cfg.decode_cache_length, positions,
                    page_table=mask,
                    page_size=cfg.decode_page_size,
                    num_pages=cfg.decode_num_pages,
                    attention_impl=cfg.decode_attention_impl,
                    kv_cache_dtype=cfg.decode_kv_cache_dtype,
                    mesh=cfg.decode_tp_mesh,
                )
            else:
                # Incremental decoding through the shared flax-cache write path
                # (ops/attention.update_decode_cache).
                k_all, v_all, decode_mask = update_decode_cache(self, k, v, cfg.decode_cache_length, pad_mask=mask)
                out = dot_product_attention(q, k_all, v_all, mask=decode_mask, causal=False)
        else:
            out = dot_product_attention(q, k, v, mask=mask, causal=True)
        return nn.Dense(cfg.hidden_size, use_bias=False, name="wo")(out.reshape(b, s, hq * d))


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, hidden):
        cfg = self.config
        gate = nn.Dense(cfg.intermediate_size, use_bias=False, name="w_gate")(hidden)
        up = nn.Dense(cfg.intermediate_size, use_bias=False, name="w_up")(hidden)
        return nn.Dense(cfg.hidden_size, use_bias=False, name="w_down")(nn.silu(gate) * up)


class LlamaLayer(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, hidden, positions, mask):
        cfg = self.config
        attn = LlamaAttention(cfg, name="attention")(RMSNorm(cfg.rms_norm_eps, name="input_norm")(hidden), positions, mask)
        hidden = constrain_activation(hidden + attn)
        mlp = LlamaMLP(cfg, name="mlp")(RMSNorm(cfg.rms_norm_eps, name="post_attn_norm")(hidden))
        return constrain_activation(hidden + mlp)


class _ScanLayerBody(nn.Module):
    """nn.scan body: carry = hidden, (positions, mask) broadcast, no per-step output."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, carry, positions, mask):
        return LlamaLayer(self.config, name="layer")(carry, positions, mask), None


class LlamaForCausalLM(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, positions=None, logits_at=None):
        cfg = self.config
        b, s = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        hidden = constrain_activation(nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens")(input_ids))
        if cfg.scan_layers:
            # One compiled layer body scanned over a stacked param axis — the
            # compile-time answer to deep stacks (XLA sees a single layer).
            scan_layer = nn.scan(
                maybe_remat(_ScanLayerBody),
                variable_axes={"params": 0, "cache": 0},
                split_rngs={"params": True},
                in_axes=(nn.broadcast, nn.broadcast),
                length=cfg.num_hidden_layers,
            )
            hidden, _ = scan_layer(cfg, name="layers")(hidden, positions, attention_mask)
        else:
            Layer = maybe_remat(LlamaLayer)
            for i in range(cfg.num_hidden_layers):
                hidden = Layer(cfg, name=f"layer_{i}")(hidden, positions, attention_mask)
        hidden = RMSNorm(cfg.rms_norm_eps, name="final_norm")(rows_for_head(hidden, logits_at))
        if cfg.tie_word_embeddings:
            embed = self.variables["params"]["embed_tokens"]["embedding"]
            return hidden @ embed.T
        return nn.Dense(cfg.vocab_size, use_bias=False, name="lm_head")(hidden)


def causal_lm_loss(params, batch, apply_fn):
    """Next-token cross-entropy with shift; ignores positions where labels < 0."""
    logits = apply_fn(params, batch["input_ids"], batch.get("attention_mask"))
    labels = batch.get("labels", batch["input_ids"])
    shift_logits = logits[:, :-1].astype(jnp.float32)
    shift_labels = labels[:, 1:]
    logp = jax.nn.log_softmax(shift_logits, axis=-1)
    valid = (shift_labels >= 0).astype(jnp.float32)
    safe_labels = jnp.maximum(shift_labels, 0)
    nll = -jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
    return (nll * valid).sum() / jnp.maximum(valid.sum(), 1.0)


def create_llama_model(
    config: Optional[LlamaConfig] = None, rng=None, seq_len: int = 2048, param_dtype=None
) -> Model:
    config = config or llama_tiny()
    if rng is None:
        rng = jax.random.key(0)
    module = LlamaForCausalLM(config)
    sample = jnp.zeros((1, min(seq_len, config.max_position_embeddings)), dtype=jnp.int32)
    params = module.init(rng, sample)
    if param_dtype is not None:
        dtype = jnp.dtype(param_dtype)
        params = jax.tree_util.tree_map(
            lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, params
        )
    return Model.from_flax(module, params, loss_fn=causal_lm_loss, sharding_rules=LLAMA_SHARDING_RULES)


class LlamaLayeredApply:
    """LayeredApply protocol for layer-streamed big-model inference
    (accelerate_tpu.big_modeling): run Llama models larger than HBM by streaming one
    layer's weights at a time while the previous layer computes."""

    def __init__(self, config: LlamaConfig):
        self.config = config

    def _layer_names(self, params):
        inner = params["params"]
        return sorted(
            (k for k in inner if k.startswith("layer_") and k != "layers"),
            key=lambda s: int(s.split("_")[1]),
        )

    def split(self, params):
        import jax

        inner = params["params"]
        prelude = {"params": {"embed_tokens": inner["embed_tokens"]}}
        if "layers" in inner:
            # scan_layers=True: stacked [L, ...] params under layers/layer; slice one
            # layer per step.
            stacked = inner["layers"]["layer"]
            layers = [
                {"params": jax.tree_util.tree_map(lambda x: x[i], stacked)}
                for i in range(self.config.num_hidden_layers)
            ]
        else:
            layers = [{"params": inner[name]} for name in self._layer_names(params)]
        tail_keys = {"final_norm"} | ({"lm_head"} if "lm_head" in inner else set())
        if self.config.tie_word_embeddings:
            # Tied head: the tail needs the embedding matrix for hidden @ E^T.
            tail_keys.add("embed_tokens")
        tail = {"params": {k: inner[k] for k in tail_keys if k in inner}}
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
        hidden = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens").apply(
            {"params": {"embedding": prelude_params["params"]["embed_tokens"]["embedding"]}}, input_ids
        )
        return (hidden, positions, attention_mask)

    def apply_layer(self, layer_params, carry):
        hidden, positions, mask = carry
        hidden = LlamaLayer(self.config).apply(layer_params, hidden, positions, mask)
        return (hidden, positions, mask)

    def apply_tail(self, tail_params, carry):
        cfg = self.config
        hidden, _, _ = carry
        hidden = RMSNorm(cfg.rms_norm_eps).apply({"params": tail_params["params"]["final_norm"]}, hidden)
        if "lm_head" in tail_params["params"]:
            return nn.Dense(cfg.vocab_size, use_bias=False).apply(
                {"params": tail_params["params"]["lm_head"]}, hidden
            )
        if cfg.tie_word_embeddings:
            embed = tail_params["params"]["embed_tokens"]["embedding"]
            return hidden @ embed.T
        return hidden


def llama3_8b() -> LlamaConfig:
    return LlamaConfig()


def llama3_70b() -> LlamaConfig:
    """The big-model-inference flagship size (BASELINE.json: Llama-3-70B
    device_map='auto' across pod)."""
    return LlamaConfig(
        hidden_size=8192,
        intermediate_size=28672,
        num_hidden_layers=80,
        num_attention_heads=64,
        num_key_value_heads=8,
    )


def mistral_7b() -> LlamaConfig:
    """Mistral-7B dims (BASELINE.json: ZeRO-3→GSPMD config). Same decoder family;
    sliding-window attention degenerates to full attention at seq <= 4096."""
    return LlamaConfig(
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_hidden_layers=32,
        num_attention_heads=32,
        num_key_value_heads=8,
        max_position_embeddings=32768,
        rope_theta=1000000.0,
    )


def llama_1b() -> LlamaConfig:
    return LlamaConfig(
        vocab_size=128256,
        hidden_size=2048,
        intermediate_size=8192,
        num_hidden_layers=16,
        num_attention_heads=32,
        num_key_value_heads=8,
    )


def llama_tiny() -> LlamaConfig:
    """Test-size config."""
    return LlamaConfig(
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=256,
        rope_theta=10000.0,
    )
