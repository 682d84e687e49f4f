"""The program's mixed-attention expert decoder
(bluefog_tpu.models.transformer.MixedAttentionMoELM) with its layers of the
gated kind, at the configuration's sizes: a head count, a window and a rotary
a layer from the configuration's per-layer lists, the leading dense layer, the
chip's share of the experts beside the shared one, the chunked loss over the
vocabulary slice.  `apply_fn` returns the scalar loss, so the loss handed to
the library's step is the identity (`training.make_lm_loss_fns`)."""

import functools

import jax.numpy as jnp

from bluefog_tpu.kernels.flash_attention import flash_attention
from bluefog_tpu.models.transformer import MixedAttentionMoELM, rotary_frequencies
from bluefog_tpu.training import make_lm_loss_fns

# query rows x keys of a kernel's tile at the timed size, by the layer's kind:
# the fastest of each kind's sweep (my chip runs, PR 35; PERF.md section 6)
BLOCKS = {"sliding_attention": (512, 512), "full_attention": (1024, 1024)}


def rotary(sizes, kind):
    r = sizes["rope_parameters"][kind]
    dims = int(sizes["head_dim"] * r["partial_rotary_factor"])
    if r["rope_type"] == "default":
        return rotary_frequencies(dims, r["rope_theta"])
    return rotary_frequencies(
        dims, r["rope_theta"], factor=r["factor"],
        original_max=r["original_max_position_embeddings"],
        beta_fast=r["beta_fast"], beta_slow=r["beta_slow"])


def build(sizes):
    n, seq = sizes["num_hidden_layers"], sizes["seq_len"]
    kinds = sizes["layer_types"][:n]
    # a quarter of the sequence in a rehearsal, so that the band spans several
    # blocks there too
    blocks = BLOCKS if seq >= 4096 else dict.fromkeys(BLOCKS, (max(8, seq // 4),) * 2)

    def attention(q, k, v, window):
        bq, bk = blocks["full_attention" if window is None else "sliding_attention"]
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=bq, block_k=bk)

    model = MixedAttentionMoELM(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_heads=max(sizes["num_attention_heads_per_layer"][:n]),
        num_kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        layer_windows=tuple(sizes["sliding_window"] if kind == "sliding_attention"
                            else None for kind in kinds),
        layer_heads=tuple(sizes["num_attention_heads_per_layer"][:n]),
        layer_rotary=tuple(rotary(sizes, kind) for kind in kinds),
        layer_dense_dff=tuple(
            sizes["intermediate_size"] if kind == "dense" else None
            for kind in sizes["mlp_layer_types"][:n]),
        num_experts=sizes["num_experts"], top_k=sizes["num_experts_per_tok"],
        experts_held=tuple(range(sizes["num_experts_held"])),
        expert_dff=sizes["moe_intermediate_size"],
        shared_dff=sizes["shared_expert_intermediate_size"],
        routed_scale=sizes["moe_routed_scaling_factor"],
        head_chunks=max(2, seq // 1024), dtype=jnp.bfloat16,
        attention_fn=attention)
    apply_fn, loss_fn = make_lm_loss_fns(model)
    return {"apply_fn": apply_fn, "has_batch_stats": False, "model": model,
            "loss_fn": loss_fn}
