"""BERT: the benchmark's seeded weights, handed to the program as the `Model`
bundle its trainer takes. The only file of this family that imports the program."""

from __future__ import annotations


def build_model(config: dict, params, dtype: str):
    from accelerate_tpu.modeling import Model
    from accelerate_tpu.models.bert import (
        BERT_SHARDING_RULES, BertConfig, BertForSequenceClassification, sequence_classification_loss)

    module = BertForSequenceClassification(BertConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        max_position_embeddings=config["max_position_embeddings"],
        type_vocab_size=config["type_vocab_size"], layer_norm_eps=config["layer_norm_eps"],
        num_labels=config["num_labels"]))
    return Model.from_flax(module, params, loss_fn=sequence_classification_loss,
                           sharding_rules=BERT_SHARDING_RULES)
