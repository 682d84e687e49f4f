"""The program's BERT encoder (bluefog_tpu.models.transformer) at the
configuration's sizes."""

import jax.numpy as jnp

from bluefog_tpu.models.transformer import BertEncoder


def build(sizes):
    model = BertEncoder(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"], dff=sizes["intermediate_size"],
        max_len=sizes["max_position_embeddings"],
        num_classes=sizes["num_labels"], dtype=jnp.bfloat16)
    return {"apply_fn": model.apply, "has_batch_stats": False, "model": model}
