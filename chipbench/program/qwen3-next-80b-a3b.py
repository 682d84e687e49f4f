"""The program's gated-delta-rule expert decoder
(bluefog_tpu.models.hybrid.GatedDeltaMoELM) at the configuration's sizes: the
layers held named by their published index, a linear layer (the gated delta
rule with one decay a head through the `gdn_intra_fwd` / `gdn_intra_bwd`
kernels and the walk's `kda_chunk_fwd` / `kda_chunk_bwd`, 32 value heads on 16
key heads read in place) wherever `(i + 1) % full_attention_interval` is not 0,
attention with an output gate a channel, zero-centred head norms and a rotary
over a quarter of the head through the whole-sequence flash kernels where it
is; in every layer the softmax router over all the experts and the chip's
share of them beside the shared expert under its own gate; the head untied
over the vocabulary slice, every block recomputed in the backward pass.
`apply_fn` returns the chunked scalar loss, so the loss handed to the library's
step is the identity (`training.make_lm_loss_fns`)."""

import functools

import jax.numpy as jnp

from bluefog_tpu.kernels.flash_attention import flash_attention
from bluefog_tpu.models.hybrid import GatedDeltaMoELM
from bluefog_tpu.training import make_lm_loss_fns


def build(sizes):
    seq = sizes["seq_len"]
    held = sizes["published_layer_index"][:sizes["num_hidden_layers"]]
    # a quarter of the sequence in a rehearsal, so that the causal diagonal
    # cuts several blocks there too; the kernels' own blocks at the timed size
    block = None if seq >= 4096 else max(8, seq // 4)
    model = GatedDeltaMoELM(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        layer_kinds=tuple(
            "attention" if (i + 1) % sizes["full_attention_interval"] == 0 else "gdn"
            for i in held),
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        rotary_dims=int(sizes["head_dim"] * sizes["partial_rotary_factor"]),
        rope_theta=sizes["rope_theta"],
        gdn_heads=sizes["linear_num_value_heads"],
        gdn_key_heads=sizes["linear_num_key_heads"],
        gdn_key_dim=sizes["linear_key_head_dim"],
        gdn_value_dim=sizes["linear_value_head_dim"],
        num_experts=sizes["num_experts"], top_k=sizes["num_experts_per_tok"],
        experts_held=tuple(range(sizes["num_experts_held"])),
        expert_dff=sizes["moe_intermediate_size"],
        shared_dff=sizes["shared_expert_intermediate_size"],
        conv_width=sizes["linear_conv_kernel_dim"], chunk=sizes["gdn_chunk_size"],
        eps=sizes["rms_norm_eps"], tie_embeddings=False, remat=True,
        head_chunks=max(2, seq // 1024), dtype=jnp.bfloat16,
        attention_fn=functools.partial(
            flash_attention, causal=True, block_q=block, block_k=block))
    apply_fn, loss_fn = make_lm_loss_fns(model)
    return {"apply_fn": apply_fn, "has_batch_stats": False, "model": model,
            "loss_fn": loss_fn}
