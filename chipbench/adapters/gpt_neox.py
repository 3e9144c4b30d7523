"""GPT-NeoX: the benchmark's seeded weights, handed to the program as the
`Model` bundle its engine and trainer take. The only file of this family that
imports the program."""

from __future__ import annotations


def program_config(config: dict, dtype: str):
    from accelerate_tpu.models.gpt_neox import GPTNeoXConfig

    return GPTNeoXConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"], rotary_pct=config["rotary_pct"],
        rope_theta=float(config["rotary_emb_base"]),
        max_position_embeddings=config["max_position_embeddings"],
        layer_norm_eps=config["layer_norm_eps"],
        use_parallel_residual=config["use_parallel_residual"], param_dtype=dtype,
    )


def build_model(config: dict, params, dtype: str):
    from accelerate_tpu.modeling import Model
    from accelerate_tpu.models.gpt_neox import GPT_NEOX_SHARDING_RULES, GPTNeoXForCausalLM
    from accelerate_tpu.models.llama import causal_lm_loss

    module = GPTNeoXForCausalLM(program_config(config, dtype))
    return Model.from_flax(module, params, loss_fn=causal_lm_loss,
                           sharding_rules=GPT_NEOX_SHARDING_RULES)
