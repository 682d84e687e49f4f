"""The program's short-convolution expert decoder
(bluefog_tpu.models.hybrid.ShortConvMoELM) at the configuration's sizes: the
layers held named by their published index, a gated short convolution through
the `short_conv_fwd` / `short_conv_bwd` kernels where the published table says
`conv`, grouped-query attention with a norm a head on q and k and a half-split
rotary through the whole-sequence flash kernels where it says
`full_attention`; the dense gated MLP in the layers under `num_dense_layers`,
in every later one the sigmoid router with a bias in the choice and the chip's
share of the experts, no shared expert; the head tied to the embedding over
the vocabulary slice, every block recomputed in the backward pass.  `apply_fn`
returns the chunked scalar loss, so the loss handed to the library's step is
the identity (`training.make_lm_loss_fns`)."""

import functools

import jax.numpy as jnp

from bluefog_tpu.kernels.flash_attention import flash_attention
from bluefog_tpu.models.hybrid import ShortConvMoELM
from bluefog_tpu.training import make_lm_loss_fns

KINDS = {"conv": "conv", "full_attention": "attention"}


def build(sizes):
    seq = sizes["seq_len"]
    held = sizes["published_layer_index"][:sizes["num_hidden_layers"]]
    # a quarter of the sequence in a rehearsal, so that the causal diagonal
    # cuts several blocks there too; the kernels' own blocks at the timed size
    block = None if seq >= 4096 else max(8, seq // 4)
    model = ShortConvMoELM(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        layer_kinds=tuple(KINDS[sizes["layer_types"][i]] for i in held),
        layer_dense=tuple(i < sizes["num_dense_layers"] for i in held),
        dff=sizes["intermediate_size"], num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["hidden_size"] // sizes["num_attention_heads"],
        rope_theta=sizes["rope_theta"], num_experts=sizes["num_experts"],
        top_k=sizes["num_experts_per_tok"],
        experts_held=tuple(range(sizes["num_experts_held"])),
        expert_dff=sizes["moe_intermediate_size"],
        routed_scale=sizes["routed_scaling_factor"], route_eps=1e-6, shared_dff=0,
        conv_width=sizes["conv_L_cache"], eps=sizes["norm_eps"], tie_embeddings=True,
        remat=True, head_chunks=max(2, seq // 1024), dtype=jnp.bfloat16,
        attention_fn=functools.partial(
            flash_attention, causal=True, block_q=block, block_k=block))
    apply_fn, loss_fn = make_lm_loss_fns(model)
    return {"apply_fn": apply_fn, "has_batch_stats": False, "model": model,
            "loss_fn": loss_fn}
