"""Latent-attention, sparse-expert decoder: the DeepSeek-V3 layer as Kimi-VL-A3B's
language model publishes it (`text_config` of
https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct/blob/main/config.json)
and as Xing4.0-29B-A4B does
(https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json),
which adds low-rank queries, YaRN positions and a residual path of four
streams. Text only: no vision tower, no projector, no next-token module.

Attention is multi-head LATENT attention (MLA): queries are heads of
`qk_nope_head_dim + qk_rope_head_dim`, full rank (`q_lora_rank` None: one
`wq`) or low rank (`q = RMSNorm(h W_qa) W_qb`, scope `mla_q_lora`); keys and
values are decompressed from ONE row a token, `[RMSNorm(c) | RoPE(k_pe)]` of
`kv_lora_rank + qk_rope_head_dim` values, and that row (zero-padded to whole
128-lane tiles, `LatentMoEConfig.decode_kv_row_values`) is all the cache
holds. Two forms of the same mathematics:

  - decompressed (training, prefill, the dense decode cache): `[k_nope | v] =
    c . W_kvb` for every cached row, `k = [k_nope | k_pe]` with `k_pe` shared
    by all heads, ordinary attention at scale `softmax_scale`;
  - absorbed (slot decode and verify blocks against the page pool): `W_kvb`'s
    key half moves onto the query, `q_abs = q_nope . W_kvb^K[h]^T`, so the
    scores are `[q_abs | q_pe] . row` and the output `probs . c` is lifted by
    `W_kvb`'s value half afterwards — the pool is read as it lies, one gather a
    block (`ops.attention.slot_cache_attention` with `v=None`).

`rope_scaling` `{"type": "yarn", ...}` is DeepSeek-V3's YaRN: interpolated
frequencies (`yarn_inv_freq`) at every position, and `softmax_scale` times
`(0.1 mscale_all_dim ln(factor) + 1)^2`; the same in both forms.

The first `first_k_dense_replace` layers are a dense SwiGLU; every later layer
is `parallel.expert.dropless_expert_ffn` over `n_routed_experts` sigmoid-routed
experts beside a shared expert of `n_shared_experts` times their width.

`hc_mult` n > 1 widens the residual path to n streams, carried as ONE row of
`n * hidden_size` values a token and mixed around every attention and every
feed-forward by `ops.hyper_connection` (`hc_pre` / `hc_post`; float32 maps
`phi_t`, `alpha`, `bias` a sub-layer, which int8 weights leave alone): the
embedding repeated n times goes in, the streams' sum comes out. `hc_mult` 1 is
the plain residual and has no map parameters.

Departures from the published code: RoPE pairs dimension i with i + d/2
(half-split) where the published code pairs 2i with 2i + 1 — the same function
up to a fixed permutation of the rope columns of `wq` / `wq_b` and `wkv_a`.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..modeling import Model
from ..ops import frontier_attention as frontier
from ..ops.attention import dot_product_attention, slot_cache_attention, update_decode_cache
from ..ops.hyper_connection import hc_post, hc_pre, map_count
from ..ops.quantization import dequantize_weight_int8, is_quantized_kernel
from ..ops.remat import maybe_remat
from ..parallel.expert import EXPERT_SHARDING_RULES, dropless_expert_ffn, sigmoid_top_k_routing
from ..parallel.sharding import constrain_activation
from .llama import RMSNorm, ServedConfig, causal_lm_loss, rotary_embedding, rows_for_head

LATENT_MOE_SHARDING_RULES = [
    (r"(wq|wq_b|wkv_b)/kernel", (None, "model")),
    (r"wo/kernel", ("model", None)),
    (r"(mlp|shared)/(w_gate|w_up)/kernel", (None, "model")),
    (r"(mlp|shared)/w_down/kernel", ("model", None)),
    (r"embed_tokens/embedding", ("model", None)),
    (r"lm_head/kernel", (None, "model")),
    (r"(wkv_a|wq_a|router)/kernel", ()),  # the latent rows and the router are whole on every device
    (r"hc_(attn|ffn)/(phi_t|alpha|bias)", ()),  # and so are the residual streams' maps
] + EXPERT_SHARDING_RULES


@dataclass
class LatentMoEConfig(ServedConfig):
    """Keys as the published config names them; defaults are Kimi-VL-A3B's."""

    vocab_size: int = 163840
    hidden_size: int = 2048
    intermediate_size: int = 11264  # the dense layers' SwiGLU width
    moe_intermediate_size: int = 1408  # one routed expert's width
    num_hidden_layers: int = 27
    num_attention_heads: int = 16
    n_shared_experts: int = 2
    n_routed_experts: int = 64
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.446
    norm_topk_prob: bool = True
    first_k_dense_replace: int = 1
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    max_position_embeddings: int = 131072
    rope_theta: float = 800000.0
    rms_norm_eps: float = 1e-5
    # None: full-rank queries (one `wq`). A rank: `q = RMSNorm(h W_qa) W_qb`.
    q_lora_rank: Optional[int] = None
    # None, or DeepSeek-V3's YaRN: {"type": "yarn", "factor", "beta_fast",
    # "beta_slow", "mscale", "mscale_all_dim", "original_max_position_embeddings"}.
    rope_scaling: Optional[dict] = None
    # Residual streams (1: the plain residual, no map parameters), the Sinkhorn
    # turns of their mixing matrix, the epsilon of the flattened RMS and of the
    # turns' divisors, and the clamp of the matrix's logits before `exp`.
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # Serving, beyond `ServedConfig`: int8 weights. There is no
    # `decode_kv_cache_dtype` and no `decode_tp_mesh`: a quantized pool and a
    # tensor-parallel split of latent rows are not built, and the engine's
    # admission says so.
    weight_dtype: str = "bf16"
    param_dtype: str = "float32"

    def __post_init__(self):
        super().__post_init__()
        if self.rope_scaling is not None and self.rope_scaling.get("type") != "yarn":
            raise ValueError(f"rope_scaling {self.rope_scaling!r}: only DeepSeek-V3's \"yarn\" is built")
        if self.hc_mult < 1 or map_count(self.hc_mult) > 128:
            raise ValueError(f"hc_mult={self.hc_mult}: 1 to 10 residual streams")

    @property
    def hc_sublayers(self) -> int:
        """The stream mixes a token passes: one around every attention and every
        feed-forward; none on the plain residual."""
        return 2 * self.num_hidden_layers if self.hc_mult > 1 else 0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        """`1 / sqrt(qk_head_dim)`, times YaRN's `mscale^2` where it applies."""
        scale = 1.0 / math.sqrt(self.qk_head_dim)
        if self.rope_scaling is not None:
            scale *= _yarn_mscale(self.rope_scaling["factor"], self.rope_scaling.get("mscale_all_dim", 0)) ** 2
        return scale

    @property
    def decode_kv_row_values(self) -> int:
        """Values the cache holds a token a layer: the latent row `[c | k_pe]`,
        zero-padded to whole 128-lane tiles (576 -> 640). A TPU array's minor
        axis is laid out in tiles of 128: at 576 the compiler either pads it to
        640 itself or, to save that, lays the pool out page-minor and copies it
        whole (2.7 GB) in every program that gathers pages. The padding is made
        here, where it is counted. Its presence is how the engine knows the
        cache is latent."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def full_head_kv_values(self) -> int:
        """What a cache of decompressed keys and values would hold instead."""
        return self.num_attention_heads * (self.qk_head_dim + self.v_head_dim)

    def prefill_walks_frontier(self, rows: int, window: Optional[int] = None) -> bool:
        """Whether `rows` new tokens prefilled into a dense cache of `window`
        positions (`decode_cache_length` where none is given) attend through
        `ops.frontier_attention`: on a TPU, more rows than one, shapes the
        kernel takes. The module asks it as it is traced, and an engine for
        its insert's span."""
        return frontier.frontier_serves(
            rows, window or self.decode_cache_length, self.qk_nope_head_dim, self.v_head_dim,
            self.qk_rope_head_dim, self._pdtype.itemsize)

    def prefill_key_blocks(self, cur: int, rows: int, window: int) -> Optional[Tuple[int, int]]:
        """(key blocks such a prefill's attention visits, key blocks the window
        holds) a layer and a head, for `rows` new tokens behind `cur` cached
        ones; None where the prefill is the masked XLA attention."""
        if not self.prefill_walks_frontier(rows, window):
            return None
        return frontier.frontier_key_blocks(cur, rows, window)

    @property
    def num_moe_layers(self) -> int:
        return max(self.num_hidden_layers - self.first_k_dense_replace, 0)

    def insert_span_counts(self, bucket: int, suffix_tokens: int, matched_len: int, window: int) -> dict:
        """`routed_pairs`: the (token, expert) pairs the bucket sends through the
        routed experts, pads included — dropless routing computes every one.
        `hc_streams` / `hc_rows` as a chunk's. `attn_key_blocks` /
        `attn_key_blocks_window`: the key blocks the attention visits a layer
        and a head, and those the slot's window holds, where the prefill walks
        the causal frontier (`prefill_key_blocks`)."""
        counts = {"routed_pairs": bucket * self.num_experts_per_tok * self.num_moe_layers} if self.num_moe_layers else {}
        counts.update(self.chunk_span_counts(bucket))
        blocks = self.prefill_key_blocks(matched_len, bucket, window)
        if blocks is not None:
            counts.update(attn_key_blocks=blocks[0], attn_key_blocks_window=blocks[1])
        return counts

    def chunk_span_counts(self, rows: int) -> dict:
        """`hc_streams`, and `hc_rows`: the rows the streams' mixes process — the
        program's rows times the sub-layers. Nothing on the plain residual."""
        return {"hc_streams": self.hc_mult, "hc_rows": rows * self.hc_sublayers} if self.hc_sublayers else {}

    @property
    def _pdtype(self):
        return jnp.dtype(self.param_dtype)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 or not mscale else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(scaling: dict, dim: int, theta: float) -> Tuple[int, int]:
    """The rope pairs between which YaRN's ramp runs: below `low` a pair keeps
    its frequency, from `high` on it is interpolated whole."""
    def pair_of(rotations: float) -> float:
        return dim * math.log(scaling["original_max_position_embeddings"] / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low, high = math.floor(pair_of(scaling["beta_fast"])), math.ceil(pair_of(scaling["beta_slow"]))
    return max(low, 0), min(high, dim - 1)


def yarn_inv_freq(scaling: dict, dim: int, theta: float) -> np.ndarray:
    """`inv_freq_i = theta^(-2i/dim) * (m_i + (1 - m_i) / factor)` over the
    `dim / 2` pairs, `m_i = 1 - clip((i - low) / (high - low), 0, 1)`."""
    low, high = yarn_correction_range(scaling, dim, theta)
    pairs = np.arange(dim // 2, dtype=np.float64)
    keep = 1.0 - np.clip((pairs - low) / max(high - low, 0.001), 0.0, 1.0)
    return (theta ** (-2.0 * pairs / dim) * (keep + (1.0 - keep) / scaling["factor"])).astype(np.float32)


class Kernel(nn.Module):
    """A weight the family multiplies by hand (an absorbed projection, a stack
    of expert matrices), stored as `<name>/kernel` like a `Dense`'s so that the
    engine's int8 weights (`quantize_params_int8`) find it; a quantized entry
    is dequantized where it is used."""

    shape: Tuple[int, ...]
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, dtype):
        if self.has_variable("params", "kernel"):
            stored = self.get_variable("params", "kernel")
            if is_quantized_kernel(stored):
                return dequantize_weight_int8(stored, dtype)
        init = nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=-2, out_axis=-1)
        return self.param("kernel", init, self.shape, self.param_dtype).astype(dtype)


def _dense(features: int, cfg: LatentMoEConfig, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, param_dtype=cfg._pdtype, name=name)


class LatentAttention(nn.Module):
    config: LatentMoEConfig

    @nn.compact
    def __call__(self, hidden, positions, mask):
        cfg = self.config
        b, s, _ = hidden.shape
        heads, nope, rope = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        rank, vd = cfg.kv_lora_rank, cfg.v_head_dim
        scale = cfg.softmax_scale

        if cfg.q_lora_rank:
            with jax.named_scope("mla_q_lora"):
                c_q = RMSNorm(cfg.rms_norm_eps, name="q_norm")(_dense(cfg.q_lora_rank, cfg, "wq_a")(hidden))
                q = _dense(heads * cfg.qk_head_dim, cfg, "wq_b")(c_q)
        else:
            q = _dense(heads * cfg.qk_head_dim, cfg, "wq")(hidden)
        q = q.reshape(b, s, heads, cfg.qk_head_dim)
        inv_freq, rope_mscale = None, 1.0
        if cfg.rope_scaling is not None:
            inv_freq = yarn_inv_freq(cfg.rope_scaling, rope, cfg.rope_theta)
            rope_mscale = (_yarn_mscale(cfg.rope_scaling["factor"], cfg.rope_scaling.get("mscale", 1))
                           / _yarn_mscale(cfg.rope_scaling["factor"], cfg.rope_scaling.get("mscale_all_dim", 0)))
        q_nope, q_pe = q[..., :nope], rotary_embedding(q[..., nope:], positions, cfg.rope_theta, inv_freq)
        row = _dense(rank + rope, cfg, "wkv_a")(hidden)
        c = RMSNorm(cfg.rms_norm_eps, name="kv_norm")(row[..., :rank])
        k_pe = rotary_embedding(row[..., None, rank:], positions, cfg.rope_theta, inv_freq)[:, :, 0]  # one for all heads
        if rope_mscale != 1.0:  # YaRN scales cos and sin alike: both rotated halves
            q_pe, k_pe = q_pe * rope_mscale, k_pe * rope_mscale
        pad = cfg.decode_kv_row_values - rank - rope  # zeros that make the row whole 128-lane tiles
        row = jnp.concatenate([c, k_pe, jnp.zeros((b, s, pad), c.dtype)], axis=-1)  # what the cache holds
        w_kvb = Kernel((rank, heads * (nope + vd)), cfg._pdtype, name="wkv_b")(hidden.dtype)
        w_kvb = w_kvb.reshape(rank, heads, nope + vd)

        if cfg.decode_cache_length and cfg.decode_slot_cache:
            with jax.named_scope("mla_absorb"):
                q_abs = jnp.einsum("bshn,rhn->bshr", q_nope, w_kvb[..., :nope])
                q_row = jnp.concatenate([q_abs, q_pe, jnp.zeros((b, s, heads, pad), q.dtype)], axis=-1)
            with jax.named_scope("latent_read"):
                out_latent = slot_cache_attention(
                    self, q_row, row, None, cfg.decode_cache_length, positions,
                    page_table=mask, page_size=cfg.decode_page_size,
                    num_pages=cfg.decode_num_pages, attention_impl=cfg.decode_attention_impl,
                    scale=scale, value_dim=rank,
                )
            with jax.named_scope("mla_absorb"):
                out = jnp.einsum("bshr,rhv->bshv", out_latent, w_kvb[..., nope:])
        else:
            decode_mask = mask
            if cfg.decode_cache_length:
                row, _, decode_mask = update_decode_cache(self, row, None, cfg.decode_cache_length, pad_mask=mask)
            # A block of new rows in a cache whose mask is the causal one alone (no
            # key-padding mask, given or kept from an earlier call): on a TPU the
            # kernel that stops at each row block's causal frontier.
            if (cfg.decode_cache_length and mask is None and not self.has_variable("cache", "pad_mask")
                    and cfg.prefill_walks_frontier(s)):
                with jax.named_scope("mla_prefill"):
                    # the kernel's layouts: keys head-major, queries and values transposed as well
                    k_nope = jnp.einsum("btr,rhn->bhtn", row[..., :rank], w_kvb[..., :nope])
                    v_t = jnp.einsum("btr,rhn->bhnt", row[..., :rank], w_kvb[..., nope:])
                    q_t = jnp.concatenate([q_nope, q_pe], axis=-1).transpose(0, 2, 3, 1)
                    out = frontier.frontier_attention(
                        q_t, k_nope, v_t, self.get_variable("cache", "cache_index") - s,
                        scale=scale, shared_k=row[..., rank:rank + rope])  # `k_pe`: one row for all heads
            else:
                kv = jnp.einsum("btr,rhn->bthn", row[..., :rank], w_kvb)
                t = row.shape[1]
                k = jnp.concatenate(
                    [kv[..., :nope], jnp.broadcast_to(row[:, :, None, rank:rank + rope], (b, t, heads, rope))],
                    axis=-1)
                q = jnp.concatenate([q_nope, q_pe], axis=-1)
                # "xla": training's causal block and a decode step's one row (and every
                # cached call off a TPU) are the masked product over all of `t`
                out = dot_product_attention(q, k, kv[..., nope:], mask=decode_mask, scale=scale,
                                            causal=not cfg.decode_cache_length, implementation="xla")
        return _dense(cfg.hidden_size, cfg, "wo")(out.reshape(b, s, heads * vd))


class SwiGLU(nn.Module):
    config: LatentMoEConfig
    width: int

    @nn.compact
    def __call__(self, hidden):
        cfg = self.config
        gate = _dense(self.width, cfg, "w_gate")(hidden)
        up = _dense(self.width, cfg, "w_up")(hidden)
        return _dense(cfg.hidden_size, cfg, "w_down")(nn.silu(gate) * up)


class RoutedExperts(nn.Module):
    """The routed experts' three stacks of matrices, `[E, in, out]` each."""

    config: LatentMoEConfig

    @nn.compact
    def __call__(self, x, ids, weights):
        cfg = self.config
        E, h, F = cfg.n_routed_experts, cfg.hidden_size, cfg.moe_intermediate_size
        stack = lambda name, shape: Kernel(shape, cfg._pdtype, name=name)(x.dtype)  # noqa: E731
        return dropless_expert_ffn(
            x, ids, weights, stack("w_gate", (E, h, F)), stack("w_up", (E, h, F)), stack("w_down", (E, F, h)))


class DroplessMoE(nn.Module):
    """Router, routed experts and the shared expert of one layer:
    `y = sum_e w_e E_e(h) + S(h)`. Slot-decode modules keep a count in the
    cache collection, `expert_tokens` ([2, E] int32: the tokens each expert
    was given, and the dispatches in which it was given any), which the engine
    zeroes before a chunk and reads back with it."""

    config: LatentMoEConfig

    @nn.compact
    def __call__(self, hidden):
        cfg = self.config
        b, s, h = hidden.shape
        E, F = cfg.n_routed_experts, cfg.moe_intermediate_size
        x = hidden.reshape(b * s, h)
        with jax.named_scope("moe_route"):
            w_router = Kernel((h, E), cfg._pdtype, name="router")(jnp.float32)
            logits = jnp.dot(x.astype(jnp.float32), w_router, precision=jax.lax.Precision.HIGHEST)
            bias = self.param("router_bias", nn.initializers.zeros, (E,), jnp.float32)
            ids, weights = sigmoid_top_k_routing(
                logits, bias, cfg.num_experts_per_tok, cfg.routed_scaling_factor, cfg.norm_topk_prob)
        routed, counts = RoutedExperts(cfg, name="experts")(x, ids, weights)
        if cfg.decode_slot_cache:
            seen = self.variable("cache", "expert_tokens", jnp.zeros, (2, E), jnp.int32)
            seen.value = seen.value + jnp.stack([counts, (counts > 0).astype(jnp.int32)])
        with jax.named_scope("moe_shared"):
            shared = SwiGLU(cfg, cfg.n_shared_experts * F, name="shared")(hidden)
        return routed.reshape(b, s, h) + shared


class HyperConnection(nn.Module):
    """One sub-layer's maps over the residual streams: `hc_pre` with this
    sub-layer's `phi_t`, `alpha` (pre, post, res) and `bias` (packed order), all
    float32 whatever the weights' type. streams `[b, s, n * hidden]` -> (the
    sub-layer's input `[b, s, hidden]`, the packed maps `hc_post` takes). The
    kernels serve (programs that keep a cache); a plain forward, which a
    gradient may be taken through, runs the `jax.numpy` form."""

    config: LatentMoEConfig

    @nn.compact
    def __call__(self, streams):
        cfg = self.config
        n, count, width = cfg.hc_mult, map_count(cfg.hc_mult), streams.shape[-1]
        phi_t = self.param("phi_t", nn.initializers.normal(width ** -0.5), (count, width), jnp.float32)
        alpha = self.param("alpha", nn.initializers.constant(0.01), (3,), jnp.float32)
        # starts near the plain residual: H_res close to the identity
        bias = self.param("bias", lambda _key, _shape, dtype: jnp.concatenate(
            [jnp.zeros((2 * n,), dtype), 4.0 * jnp.eye(n, dtype=dtype).reshape(-1)]), (count,), jnp.float32)
        return hc_pre(streams, phi_t, alpha, bias, n=n, iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
                      clamp=(cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max), impl=_hc_impl(cfg))


def _hc_impl(cfg: LatentMoEConfig) -> Optional[str]:
    return None if cfg.decode_cache_length else "xla"


class LatentMoELayer(nn.Module):
    config: LatentMoEConfig
    dense: bool

    def _ffn(self, normed):
        cfg = self.config
        if self.dense:
            return SwiGLU(cfg, cfg.intermediate_size, name="mlp")(normed)
        return DroplessMoE(cfg, name="moe")(normed)

    def _around(self, maps_name: str, hidden, sublayer):
        """`hidden + F(hidden)` for the plain residual; for n streams (`hidden`
        is a token's streams side by side, [b, s, n * hidden_size]) the
        sub-layer's own maps mix them into its input and its output back."""
        cfg = self.config
        if cfg.hc_mult == 1:
            return constrain_activation(hidden + sublayer(hidden))
        u, maps = HyperConnection(cfg, name=maps_name)(hidden)
        return constrain_activation(hc_post(hidden, sublayer(u), maps, n=cfg.hc_mult, impl=_hc_impl(cfg)))

    @nn.compact
    def __call__(self, hidden, positions, mask):
        cfg = self.config
        hidden = self._around("hc_attn", hidden, lambda h: LatentAttention(cfg, name="attention")(
            RMSNorm(cfg.rms_norm_eps, name="input_norm")(h), positions, mask))
        return self._around("hc_ffn", hidden, lambda h: self._ffn(
            RMSNorm(cfg.rms_norm_eps, name="post_attn_norm")(h)))


class LatentMoEForCausalLM(nn.Module):
    config: LatentMoEConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, positions=None, logits_at=None):
        cfg = self.config
        b, s = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        hidden = constrain_activation(
            nn.Embed(cfg.vocab_size, cfg.hidden_size, param_dtype=cfg._pdtype, name="embed_tokens")(input_ids)
        )
        if cfg.hc_mult > 1:  # every stream starts as the embedding
            hidden = jnp.tile(hidden, (1, 1, cfg.hc_mult))
        Layer = maybe_remat(LatentMoELayer)
        for i in range(cfg.num_hidden_layers):
            hidden = Layer(cfg, i < cfg.first_k_dense_replace, name=f"layer_{i}")(
                hidden, positions, attention_mask)
        hidden = rows_for_head(hidden, logits_at)
        if cfg.hc_mult > 1:  # and their sum is what the head reads
            c = cfg.hidden_size
            hidden = sum(hidden[..., j * c:(j + 1) * c].astype(jnp.float32)
                         for j in range(cfg.hc_mult)).astype(hidden.dtype)
        hidden = RMSNorm(cfg.rms_norm_eps, name="final_norm")(hidden)
        return _dense(cfg.vocab_size, cfg, "lm_head")(hidden)


def create_latent_moe_model(
    config: Optional[LatentMoEConfig] = None, rng=None, seq_len: int = 2048, param_dtype=None
) -> Model:
    config = config or latent_moe_tiny()
    if param_dtype is not None:
        config = dataclasses.replace(config, param_dtype=str(jnp.dtype(param_dtype)))
    if rng is None:
        rng = jax.random.key(0)
    module = LatentMoEForCausalLM(config)
    sample = jnp.zeros((1, min(seq_len, config.max_position_embeddings, 128)), dtype=jnp.int32)
    params = jax.jit(module.init)(rng, sample)
    return Model.from_flax(module, params, loss_fn=causal_lm_loss, sharding_rules=LATENT_MOE_SHARDING_RULES)


def kimi_vl_a3b_text() -> LatentMoEConfig:
    """Kimi-VL-A3B-Instruct's language model as published: 27 layers, 15.96 B
    parameters (31.9 GB in bfloat16 — more than one v5e chip holds)."""
    return LatentMoEConfig()


def xing4_29b_a4b() -> LatentMoEConfig:
    """Xing4.0-29B-A4B as published: 40 layers, 29.5 B parameters (59 GB in
    bfloat16), low-rank queries, YaRN, four residual streams. Its next-token
    module (`num_nextn_predict_layers` 1) is not built."""
    return LatentMoEConfig(
        vocab_size=131072, hidden_size=3584, intermediate_size=9216, moe_intermediate_size=1024,
        num_hidden_layers=40, num_attention_heads=32, n_shared_experts=1, n_routed_experts=64,
        num_experts_per_tok=4, routed_scaling_factor=2.0, first_k_dense_replace=2, kv_lora_rank=512,
        q_lora_rank=768, max_position_embeddings=262144, rope_theta=10000.0, rms_norm_eps=1e-6,
        hc_mult=4, rope_scaling={"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                                 "mscale_all_dim": 1, "original_max_position_embeddings": 4096},
    )


def latent_moe_hc_tiny() -> LatentMoEConfig:
    """The tiny preset with what Xing4.0 adds: four streams, a query rank,
    YaRN (its ramp inside the 4 rope pairs) and 2 leading dense layers."""
    return dataclasses.replace(
        latent_moe_tiny(), num_hidden_layers=4, first_k_dense_replace=2, n_shared_experts=1, q_lora_rank=24,
        hc_mult=4, rope_scaling={"type": "yarn", "factor": 8, "beta_fast": 4, "beta_slow": 1, "mscale": 1,
                                 "mscale_all_dim": 1, "original_max_position_embeddings": 32},
    )


def latent_moe_tiny() -> LatentMoEConfig:
    return LatentMoEConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=3, num_attention_heads=4, n_shared_experts=2, n_routed_experts=8,
        num_experts_per_tok=3, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, max_position_embeddings=256, rope_theta=10000.0,
    )
