"""The latent-attention, sparse-expert family (`accelerate_tpu/models/latent_moe.py`):
the benchmark's seeded weights, handed to the program as the `Model` bundle its
engine takes. The only file of this family that imports the program."""

from __future__ import annotations

#: The published keys the program's config takes under the same name.
_SAME = ("vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
         "num_hidden_layers", "num_attention_heads", "n_shared_experts", "n_routed_experts",
         "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob", "first_k_dense_replace",
         "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "max_position_embeddings", "rms_norm_eps")


def program_config(config: dict, dtype: str):
    from accelerate_tpu.models.latent_moe import LatentMoEConfig

    unsupported = {"q_lora_rank": None, "rope_scaling": None, "n_group": 1, "topk_group": 1,
                   "scoring_func": "sigmoid", "topk_method": "noaux_tc", "moe_layer_freq": 1,
                   "attention_bias": False, "tie_word_embeddings": False, "hidden_act": "silu"}
    for key, only in unsupported.items():
        if config.get(key, only) != only:
            raise ValueError(f"{key}={config[key]!r}: the program's latent_moe family has {only!r} alone")
    return LatentMoEConfig(**{k: config[k] for k in _SAME}, rope_theta=float(config["rope_theta"]),
                           param_dtype=dtype)


def build_model(config: dict, params, dtype: str):
    from accelerate_tpu.modeling import Model
    from accelerate_tpu.models.latent_moe import LATENT_MOE_SHARDING_RULES, LatentMoEForCausalLM
    from accelerate_tpu.models.llama import causal_lm_loss

    module = LatentMoEForCausalLM(program_config(config, dtype))
    return Model.from_flax(module, params, loss_fn=causal_lm_loss,
                           sharding_rules=LATENT_MOE_SHARDING_RULES)
