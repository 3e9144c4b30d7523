"""The parallel state-space and attention family (`accelerate_tpu/models/falcon_h1.py`):
the benchmark's seeded weights, handed to the program as the `Model` bundle its
engine takes. The only file of this family that imports the program."""

from __future__ import annotations

#: The published keys the program's config takes under the same name.
_SAME = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
         "num_key_value_heads", "head_dim", "max_position_embeddings", "rope_theta", "rms_norm_eps",
         "mamba_d_ssm", "mamba_n_heads", "mamba_d_head", "mamba_n_groups", "mamba_d_state", "mamba_d_conv",
         "mamba_chunk_size", "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
         "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
         "ssm_multipliers", "mlp_multipliers")


def program_config(config: dict, dtype: str):
    from accelerate_tpu.models.falcon_h1 import FalconH1Config

    unsupported = {"attention_bias": False, "mlp_bias": False, "mamba_proj_bias": False, "projectors_bias": False,
                   "mamba_conv_bias": True, "mamba_rms_norm": True, "mamba_norm_before_gate": False,
                   "tie_word_embeddings": False, "hidden_act": "silu", "rope_scaling": None,
                   "attn_layer_indices": None}
    for key, only in unsupported.items():
        if config.get(key, only) != only:
            raise ValueError(f"{key}={config[key]!r}: the program's falcon_h1 family has {only!r} alone")
    same = {k: config[k] for k in _SAME}
    same["rope_theta"] = float(same["rope_theta"])  # 1e11 as published: an integer no int32 holds
    return FalconH1Config(**same, param_dtype=dtype)


def build_model(config: dict, params, dtype: str):
    from accelerate_tpu.modeling import Model
    from accelerate_tpu.models.falcon_h1 import FALCON_H1_SHARDING_RULES, FalconH1ForCausalLM
    from accelerate_tpu.models.llama import causal_lm_loss

    module = FalconH1ForCausalLM(program_config(config, dtype))
    return Model.from_flax(module, params, loss_fn=causal_lm_loss, sharding_rules=FALCON_H1_SHARDING_RULES)
