"""In-tree model families (flax), each shipping TP sharding rules and a loss.

These cover the reference's benchmark configs (BASELINE.json): BERT (GLUE),
Llama (FSDP fine-tune + big-model inference), ResNet (cv_example)."""

from .bert import BertConfig, BertForSequenceClassification, bert_base, bert_tiny, create_bert_model
from .llama import (
    LlamaConfig,
    LlamaForCausalLM,
    ServedConfig,
    create_llama_model,
    llama3_8b,
    llama_1b,
    llama_tiny,
)
from .resnet import ResNet, ResNetConfig, create_resnet_model, resnet50, resnet_tiny
from .mixtral import (
    MixtralConfig,
    MixtralForCausalLM,
    create_mixtral_model,
    mixtral_8x7b,
    mixtral_tiny,
)
from .gptj import GPTJConfig, GPTJForCausalLM, create_gptj_model, gptj_6b, gptj_tiny
from .gpt_neox import (
    GPTNeoXConfig,
    GPTNeoXForCausalLM,
    create_gpt_neox_model,
    gpt_neox_20b,
    gpt_neox_tiny,
)
from .latent_moe import (
    LatentMoEConfig,
    LatentMoEForCausalLM,
    create_latent_moe_model,
    kimi_vl_a3b_text,
    latent_moe_hc_tiny,
    latent_moe_tiny,
    xing4_29b_a4b,
)
from .falcon_h1 import (
    FalconH1Config,
    FalconH1ForCausalLM,
    create_falcon_h1_model,
    falcon_h1_34b,
    falcon_h1_tiny,
)
from .olmo_hybrid import (
    OlmoHybridConfig,
    OlmoHybridForCausalLM,
    create_olmo_hybrid_model,
    olmo_hybrid_7b,
    olmo_hybrid_tiny,
)
from .opt import OPTConfig, OPTForCausalLM, create_opt_model, opt_30b, opt_tiny
from .t5 import (
    T5Config,
    T5ForConditionalGeneration,
    create_t5_model,
    t0pp_11b,
    t5_small_v1_0,
    t5_tiny,
    t5_tiny_v1_0,
)

# The single source of truth for named in-tree models: name -> (interchange
# family, dataclass-config factory). The estimate registry and the convert CLI
# both derive from this, so a new model registers exactly once.
MODEL_REGISTRY = {
    "bert-base": ("bert", bert_base),
    "bert-tiny": ("bert", bert_tiny),
    "llama-3-8b": ("llama", llama3_8b),
    "llama-1b": ("llama", llama_1b),
    "llama-tiny": ("llama", llama_tiny),
    "mixtral-8x7b": ("mixtral", mixtral_8x7b),
    "mixtral-tiny": ("mixtral", mixtral_tiny),
    "gptj-6b": ("gptj", gptj_6b),
    "gptj-tiny": ("gptj", gptj_tiny),
    "gpt-neox-20b": ("gpt_neox", gpt_neox_20b),
    "gpt-neox-tiny": ("gpt_neox", gpt_neox_tiny),
    "kimi-vl-a3b-text": ("latent_moe", kimi_vl_a3b_text),
    "latent-moe-tiny": ("latent_moe", latent_moe_tiny),
    "xing4-29b-a4b": ("latent_moe", xing4_29b_a4b),
    "latent-moe-hc-tiny": ("latent_moe", latent_moe_hc_tiny),
    "falcon-h1-34b": ("falcon_h1", falcon_h1_34b),
    "falcon-h1-tiny": ("falcon_h1", falcon_h1_tiny),
    "olmo-hybrid-7b": ("olmo_hybrid", olmo_hybrid_7b),
    "olmo-hybrid-tiny": ("olmo_hybrid", olmo_hybrid_tiny),
    "opt-30b": ("opt", opt_30b),
    "opt-tiny": ("opt", opt_tiny),
    "t0pp-11b": ("t5", t0pp_11b),
    "t5-tiny": ("t5", t5_tiny),
    "t5-small": ("t5", t5_small_v1_0),
    "t5-tiny-v1-0": ("t5", t5_tiny_v1_0),
}

# A published `config.json`'s `model_type` -> the in-tree family that runs its
# language model, where the two names differ.
PUBLISHED_MODEL_TYPES = {
    "xing4_0": "latent_moe",
}

# family -> Model-bundle creator (the `create_*` entry points above).
CREATE_BY_FAMILY = {
    "bert": create_bert_model,
    "llama": create_llama_model,
    "mixtral": create_mixtral_model,
    "gptj": create_gptj_model,
    "gpt_neox": create_gpt_neox_model,
    "opt": create_opt_model,
    "t5": create_t5_model,
    "latent_moe": create_latent_moe_model,
    "olmo_hybrid": create_olmo_hybrid_model,
    "falcon_h1": create_falcon_h1_model,
}

# family -> its config dataclass: which family a live bundle is (the worker's
# `spec_for_model`) and the class a config is rebuilt with from its fields.
CONFIG_BY_FAMILY = {
    "bert": BertConfig,
    "llama": LlamaConfig,
    "mixtral": MixtralConfig,
    "gptj": GPTJConfig,
    "gpt_neox": GPTNeoXConfig,
    "opt": OPTConfig,
    "t5": T5Config,
    "latent_moe": LatentMoEConfig,
    "olmo_hybrid": OlmoHybridConfig,
    "falcon_h1": FalconH1Config,
}

# family -> (flax module class name, LayeredApply class) for models shipping a
# prelude/layers/tail decomposition. Consumed by `layered_for_model`, the seam
# `Accelerator.prepare(sharding_rules="auto")` on a "pipeline" mesh and the
# `plan --mesh ... pipeline=` CLI use to get the per-layer param split that
# `plan_pipeline_stages` balances and `parallel/mpmd.py` executes. T5 is absent
# on purpose: its encoder/decoder split rides the pipeline (promote) protocol,
# not the linear-carry LayeredApply contract the MPMD runtime assumes.
LAYERED_BY_FAMILY = {
    "llama": "LlamaForCausalLM",
    "gpt_neox": "GPTNeoXForCausalLM",
    "gptj": "GPTJForCausalLM",
    "opt": "OPTForCausalLM",
}


def _layered_classes():
    from .gpt_neox import GPTNeoXLayeredApply
    from .gptj import GPTJLayeredApply
    from .llama import LlamaLayeredApply
    from .opt import OPTLayeredApply

    return {
        "LlamaForCausalLM": LlamaLayeredApply,
        "GPTNeoXForCausalLM": GPTNeoXLayeredApply,
        "GPTJForCausalLM": GPTJLayeredApply,
        "OPTForCausalLM": OPTLayeredApply,
    }


def layered_for_family(family: str, config):
    """Construct the family's `LayeredApply` from a config alone — no module,
    no weights. `split()` is pure pytree indexing, so the plan CLI can split an
    `eval_shape` tree and plan a 3D pipeline layout without materializing."""
    cls_name = LAYERED_BY_FAMILY.get(family)
    if cls_name is None:
        raise ValueError(
            f"Family {family!r} ships no LayeredApply decomposition — pipeline-"
            f"parallel planning needs one (known: {sorted(LAYERED_BY_FAMILY)}). "
            "Drop the 'pipeline' mesh axis for this model."
        )
    return _layered_classes()[cls_name](config)


def layered_for_model(model):
    """The model's `LayeredApply` decomposition, sniffed from its flax module.

    Returns the constructed LayeredApply instance, or raises ValueError when
    the model has no module / the family ships no decomposition — the caller
    (3D planner dispatch) turns that into "this model can't pipeline"."""
    module = getattr(model, "module", None)
    cls_name = type(module).__name__ if module is not None else None
    layered_cls = _layered_classes().get(cls_name or "")
    if layered_cls is None:
        known = sorted(LAYERED_BY_FAMILY.values())
        raise ValueError(
            f"No LayeredApply decomposition for module {cls_name!r} — pipeline-"
            f"parallel planning/execution needs one (known: {known}). Pass "
            "layered= explicitly or drop the 'pipeline' mesh axis."
        )
    return layered_cls(module.config)


def get_model_family(name: str):
    """(interchange family, dataclass config) for a named in-tree model."""
    key = name.lower()
    if key not in MODEL_REGISTRY:
        raise ValueError(f"Unknown in-tree model {name!r}; known: {sorted(MODEL_REGISTRY)}")
    family, factory = MODEL_REGISTRY[key]
    return family, factory()


def create_named_model(name: str, **kwargs):
    """Build the Model bundle for a registry name (create fn resolved by family)."""
    family, config = get_model_family(name)
    return CREATE_BY_FAMILY[family](config, **kwargs)


def _t5_cfg(c: T5Config) -> dict:
    return {
        "model_type": "t5",
        "vocab_size": c.vocab_size,
        "hidden_size": c.d_model,
        "d_ff": c.d_ff,
        "d_kv": c.d_kv,
        "head_dim": c.d_kv,
        "num_hidden_layers": c.num_layers + c.num_decoder_layers,
        "num_encoder_layers": c.num_layers,
        "num_decoder_layers": c.num_decoder_layers,
        "num_attention_heads": c.num_heads,
        "intermediate_size": c.d_ff,
        "is_encoder_decoder": True,
        "feed_forward_proj": c.feed_forward_proj,
        "tie_word_embeddings": c.tie_word_embeddings,
    }


def _gpt_neox_cfg(c: GPTNeoXConfig) -> dict:
    return {
        "model_type": "gpt_neox",
        "vocab_size": c.vocab_size,
        "hidden_size": c.hidden_size,
        "num_hidden_layers": c.num_hidden_layers,
        "num_attention_heads": c.num_attention_heads,
        "intermediate_size": c.intermediate_size,
        "rotary_pct": c.rotary_pct,
        "tie_word_embeddings": False,
    }


def _opt_cfg(c: OPTConfig) -> dict:
    return {
        "model_type": "opt",
        "vocab_size": c.vocab_size,
        "hidden_size": c.hidden_size,
        "num_hidden_layers": c.num_hidden_layers,
        "num_attention_heads": c.num_attention_heads,
        "intermediate_size": c.intermediate_size,
        "tie_word_embeddings": True,
    }


def _gptj_cfg(c: GPTJConfig) -> dict:
    return {
        "model_type": "gptj",
        "vocab_size": c.vocab_size,
        "hidden_size": c.hidden_size,
        "num_hidden_layers": c.num_hidden_layers,
        "num_attention_heads": c.num_attention_heads,
        "intermediate_size": c.intermediate_size,
        "rotary_dim": c.rotary_dim,
        "tie_word_embeddings": False,
    }


def _mixtral_cfg(c: MixtralConfig) -> dict:
    return {
        "model_type": "mixtral",
        "vocab_size": c.vocab_size,
        "hidden_size": c.hidden_size,
        "num_hidden_layers": c.num_hidden_layers,
        "num_attention_heads": c.num_attention_heads,
        "num_key_value_heads": c.num_key_value_heads,
        "intermediate_size": c.intermediate_size,
        "num_local_experts": c.num_local_experts,
        "num_experts_per_tok": c.num_experts_per_tok,
        "hidden_act": "silu",
        "tie_word_embeddings": False,
    }


def _latent_moe_cfg(c: LatentMoEConfig) -> dict:
    return {
        "model_type": "latent_moe",
        "vocab_size": c.vocab_size,
        "hidden_size": c.hidden_size,
        "num_hidden_layers": c.num_hidden_layers,
        "num_attention_heads": c.num_attention_heads,
        "intermediate_size": c.intermediate_size,
        "moe_intermediate_size": c.moe_intermediate_size,
        "n_routed_experts": c.n_routed_experts,
        "n_shared_experts": c.n_shared_experts,
        "num_experts_per_tok": c.num_experts_per_tok,
        "first_k_dense_replace": c.first_k_dense_replace,
        "kv_lora_rank": c.kv_lora_rank,
        "qk_nope_head_dim": c.qk_nope_head_dim,
        "qk_rope_head_dim": c.qk_rope_head_dim,
        "v_head_dim": c.v_head_dim,
        "q_lora_rank": c.q_lora_rank,
        "rope_scaling": c.rope_scaling,
        "hc_mult": c.hc_mult,
        "hidden_act": "silu",
        "tie_word_embeddings": False,
    }


def _olmo_hybrid_cfg(c: OlmoHybridConfig) -> dict:
    return {
        "model_type": "olmo_hybrid",
        "vocab_size": c.vocab_size,
        "hidden_size": c.hidden_size,
        "num_hidden_layers": c.num_hidden_layers,
        "num_attention_heads": c.num_attention_heads,
        "num_key_value_heads": c.num_key_value_heads,
        "intermediate_size": c.intermediate_size,
        "layer_types": list(c.layer_types),
        "linear_num_key_heads": c.linear_num_key_heads,
        "linear_num_value_heads": c.linear_num_value_heads,
        "linear_key_head_dim": c.linear_key_head_dim,
        "linear_value_head_dim": c.linear_value_head_dim,
        "linear_conv_kernel_dim": c.linear_conv_kernel_dim,
        "hidden_act": "silu",
        "tie_word_embeddings": False,
    }


def _falcon_h1_cfg(c: FalconH1Config) -> dict:
    return {
        "model_type": "falcon_h1",
        "vocab_size": c.vocab_size,
        "hidden_size": c.hidden_size,
        "num_hidden_layers": c.num_hidden_layers,
        "num_attention_heads": c.num_attention_heads,
        "num_key_value_heads": c.num_key_value_heads,
        "head_dim": c.head_dim,
        "intermediate_size": c.intermediate_size,
        "mamba_d_ssm": c.mamba_d_ssm,
        "mamba_n_heads": c.mamba_n_heads,
        "mamba_d_head": c.mamba_d_head,
        "mamba_n_groups": c.mamba_n_groups,
        "mamba_d_state": c.mamba_d_state,
        "mamba_d_conv": c.mamba_d_conv,
        "mamba_chunk_size": c.mamba_chunk_size,
        "hidden_act": "silu",
        "tie_word_embeddings": False,
    }


def _bert_cfg(c: BertConfig) -> dict:
    return {
        "model_type": "bert",
        "vocab_size": c.vocab_size,
        "hidden_size": c.hidden_size,
        "num_hidden_layers": c.num_hidden_layers,
        "num_attention_heads": c.num_attention_heads,
        "intermediate_size": c.intermediate_size,
        "tie_word_embeddings": True,
    }


def _llama_cfg(c: LlamaConfig) -> dict:
    return {
        "model_type": "llama",
        "vocab_size": c.vocab_size,
        "hidden_size": c.hidden_size,
        "num_hidden_layers": c.num_hidden_layers,
        "num_attention_heads": c.num_attention_heads,
        "num_key_value_heads": c.num_key_value_heads,
        "intermediate_size": c.intermediate_size,
        "hidden_act": "silu",
        "tie_word_embeddings": c.tie_word_embeddings,
    }


# family -> HF-shaped dict builder (bare references; defined above this point).
_CFG_BUILDERS = {
    "bert": _bert_cfg,
    "llama": _llama_cfg,
    "mixtral": _mixtral_cfg,
    "gptj": _gptj_cfg,
    "gpt_neox": _gpt_neox_cfg,
    "opt": _opt_cfg,
    "t5": _t5_cfg,
    "latent_moe": _latent_moe_cfg,
    "olmo_hybrid": _olmo_hybrid_cfg,
    "falcon_h1": _falcon_h1_cfg,
}


def get_model_config(name: str) -> dict:
    """HF-config.json-shaped dict for a named in-tree model (estimate CLI)."""
    family, config = get_model_family(name)
    return _CFG_BUILDERS[family](config)
