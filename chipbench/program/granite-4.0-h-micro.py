"""The program's hybrid state-space decoder
(bluefog_tpu.models.hybrid.HybridMambaLM) at the configuration's sizes: a
mixer's kind a layer from `layer_types`, the scan through the library's
chunked kernels, the attention layer through the whole-sequence flash kernels
with the shared key-value heads read in place, Granite's four multipliers, the
head tied to the embedding over the vocabulary slice, every block recomputed
in the backward pass.  `apply_fn` returns the chunked scalar loss, so the loss
handed to the library's step is the identity (`training.make_lm_loss_fns`)."""

import functools

import jax.numpy as jnp

from bluefog_tpu.kernels.flash_attention import flash_attention
from bluefog_tpu.models.hybrid import HybridMambaLM
from bluefog_tpu.training import make_lm_loss_fns


def build(sizes):
    seq = sizes["seq_len"]
    # a quarter of the sequence in a rehearsal, so that the causal diagonal
    # cuts several blocks there too; the kernels' own blocks at the timed size
    block = None if seq >= 4096 else max(8, seq // 4)
    model = HybridMambaLM(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        layer_kinds=tuple(sizes["layer_types"][:sizes["num_hidden_layers"]]),
        dff=sizes["intermediate_size"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["attention_head_dim"],
        ssm_heads=sizes["mamba_n_heads"], ssm_head_dim=sizes["mamba_d_head"],
        ssm_state=sizes["mamba_d_state"], ssm_groups=sizes["mamba_n_groups"],
        conv_width=sizes["mamba_d_conv"], chunk=sizes["mamba_chunk_size"],
        embedding_multiplier=sizes["embedding_multiplier"],
        attention_multiplier=sizes["attention_multiplier"],
        residual_multiplier=sizes["residual_multiplier"],
        logits_scaling=sizes["logits_scaling"], eps=sizes["rms_norm_eps"],
        tie_embeddings=sizes["tie_word_embeddings"], remat=True,
        head_chunks=max(2, seq // 1024), dtype=jnp.bfloat16,
        attention_fn=functools.partial(
            flash_attention, causal=True, block_q=block, block_k=block))
    apply_fn, loss_fn = make_lm_loss_fns(model)
    return {"apply_fn": apply_fn, "has_batch_stats": False, "model": model,
            "loss_fn": loss_fn}
