"""The program's mixed-attention expert decoder
(bluefog_tpu.models.transformer.MixedAttentionMoELM) at the configuration's
sizes: the per-layer pattern from the two layouts, the chip's share of the
experts, the chunked loss over the vocabulary slice.  `apply_fn` returns the
scalar loss, so the loss handed to the library's step is the identity
(`training.make_lm_loss_fns`)."""

import functools

import jax.numpy as jnp

from bluefog_tpu.kernels.flash_attention import flash_attention
from bluefog_tpu.models.transformer import MixedAttentionMoELM
from bluefog_tpu.training import make_lm_loss_fns


def build(sizes):
    windows = tuple(
        sizes["sliding_window_size"] if banded else None
        for banded in sizes["sliding_window_layout"][:sizes["num_hidden_layers"]])
    seq = sizes["seq_len"]
    # the kernels' own 1024-row blocks at the timed size; a quarter of the
    # sequence in a rehearsal, so that the band spans several blocks there too
    block = None if seq >= 4096 else max(8, seq // 4)
    model = MixedAttentionMoELM(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        layer_windows=windows, num_experts=sizes["moe_num_primary_experts"],
        top_k=sizes["moe_num_active_primary_experts"],
        experts_held=tuple(range(sizes["moe_num_primary_experts_held"])),
        expert_dff=sizes["moe_ffn_hidden_size"], rope_base=sizes["rope_theta"],
        head_chunks=max(2, seq // 1024), dtype=jnp.bfloat16,
        attention_fn=functools.partial(
            flash_attention, causal=True, block_q=block, block_k=block))
    apply_fn, loss_fn = make_lm_loss_fns(model)
    return {"apply_fn": apply_fn, "has_batch_stats": False, "model": model,
            "loss_fn": loss_fn}
