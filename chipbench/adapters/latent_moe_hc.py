"""The latent-attention, sparse-expert family with low-rank queries, YaRN and a
residual path of several streams (`accelerate_tpu/models/latent_moe.py`, the
file `adapters/latent_moe.py` hands Kimi-VL-A3B to): the benchmark's seeded
weights, handed to the program as the `Model` bundle its engine takes. The only
file of this family that imports the program."""

from __future__ import annotations

import numpy as np

#: The published keys the program's config takes under the same name.
_SAME = ("vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
         "num_hidden_layers", "num_attention_heads", "n_shared_experts", "n_routed_experts",
         "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob", "first_k_dense_replace",
         "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "max_position_embeddings", "rms_norm_eps", "hc_mult", "hc_sinkhorn_iters", "hc_eps",
         "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")


def program_config(config: dict, dtype: str):
    from accelerate_tpu.models.latent_moe import LatentMoEConfig

    unsupported = {"n_group": 1, "topk_group": 1, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
                   "moe_layer_freq": 1, "ep_size": 1, "attention_bias": False, "tie_word_embeddings": False,
                   "hidden_act": "silu"}
    for key, only in unsupported.items():
        if config.get(key, only) != only:
            raise ValueError(f"{key}={config[key]!r}: the program's latent_moe family has {only!r} alone")
    # `num_nextn_predict_layers` stays in the file as published: the next-token module is not built,
    # in the program or in the reference, and nothing here stands in for it (the file's `assumed`).
    return LatentMoEConfig(**{k: config[k] for k in _SAME}, rope_theta=float(config["rope_theta"]),
                           rope_scaling=dict(config["rope_scaling"]), param_dtype=dtype)


def program_maps(maps: dict) -> dict:
    """The reference's maps of one sub-layer as the program stores them: `Phi`
    transposed (`phi_t` [n + n + n^2, n C]) and the biases packed in the order
    of its rows."""
    return {"phi_t": np.ascontiguousarray(np.asarray(maps["phi"]).T), "alpha": np.asarray(maps["alpha"]),
            "bias": np.concatenate([np.asarray(maps[k]).reshape(-1) for k in ("b_pre", "b_post", "b_res")])}


def program_params(params: dict) -> dict:
    inner = {}
    for name, entry in params["params"].items():
        if name.startswith("layer_"):
            entry = {k: program_maps(v) if k in ("hc_attn", "hc_ffn") else v for k, v in entry.items()}
        inner[name] = entry
    return {"params": inner}


def build_model(config: dict, params, dtype: str):
    from accelerate_tpu.modeling import Model
    from accelerate_tpu.models.latent_moe import LATENT_MOE_SHARDING_RULES, LatentMoEForCausalLM
    from accelerate_tpu.models.llama import causal_lm_loss

    module = LatentMoEForCausalLM(program_config(config, dtype))
    return Model.from_flax(module, program_params(params), loss_fn=causal_lm_loss,
                           sharding_rules=LATENT_MOE_SHARDING_RULES)
