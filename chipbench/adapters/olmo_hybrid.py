"""The hybrid linear-attention family (`accelerate_tpu/models/olmo_hybrid.py`):
the benchmark's seeded weights, handed to the program as the `Model` bundle its
engine takes. The only file of this family that imports the program."""

from __future__ import annotations

#: The published keys the program's config takes under the same name.
_SAME = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
         "num_key_value_heads", "max_position_embeddings", "rms_norm_eps", "linear_num_key_heads",
         "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
         "linear_conv_kernel_dim", "linear_allow_neg_eigval")


def program_config(config: dict, dtype: str):
    from accelerate_tpu.models.olmo_hybrid import OlmoHybridConfig

    unsupported = {"attention_bias": False, "tie_word_embeddings": False, "hidden_act": "silu",
                   "rope_parameters": {"rope_theta": None}}
    for key, only in unsupported.items():
        if config.get(key, only) != only:
            raise ValueError(f"{key}={config[key]!r}: the program's olmo_hybrid family has {only!r} alone")
    return OlmoHybridConfig(**{k: config[k] for k in _SAME}, layer_types=tuple(config["layer_types"]),
                            param_dtype=dtype)


def build_model(config: dict, params, dtype: str):
    from accelerate_tpu.modeling import Model
    from accelerate_tpu.models.llama import causal_lm_loss
    from accelerate_tpu.models.olmo_hybrid import OLMO_HYBRID_SHARDING_RULES, OlmoHybridForCausalLM

    module = OlmoHybridForCausalLM(program_config(config, dtype))
    return Model.from_flax(module, params, loss_fn=causal_lm_loss,
                           sharding_rules=OLMO_HYBRID_SHARDING_RULES)
