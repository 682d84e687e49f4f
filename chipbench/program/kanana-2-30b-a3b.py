"""The program's latent-attention expert decoder
(bluefog_tpu.models.hybrid.DeltaLatentMoELM with `"mla"` in every layer) at
the configuration's sizes: latent attention through the whole-sequence flash
kernels with a query-key head of 192 beside a value head of 128, no gate on
the heads, the rotary over interleaved pairs; layer 0 the dense gated MLP,
every later layer the sigmoid router with a bias in the choice and one group,
the chip's share of the experts beside the two shared ones as one MLP, an
untied head over the vocabulary slice, every block recomputed in the backward
pass.  `apply_fn` returns the chunked scalar loss, so the loss handed to the
library's step is the identity (`training.make_lm_loss_fns`)."""

import functools

import jax.numpy as jnp

from bluefog_tpu.kernels.flash_attention import flash_attention
from bluefog_tpu.models.hybrid import DeltaLatentMoELM
from bluefog_tpu.training import make_lm_loss_fns


def build(sizes):
    n, seq = sizes["num_hidden_layers"], sizes["seq_len"]
    # a quarter of the sequence in a rehearsal, so that the causal diagonal
    # cuts several blocks there too; the kernels' own blocks at the timed size
    block = None if seq >= 4096 else max(8, seq // 4)
    model = DeltaLatentMoELM(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        layer_kinds=("mla",) * n,
        layer_dense=tuple(i < sizes["first_k_dense_replace"] for i in range(n)),
        dff=sizes["intermediate_size"], num_heads=sizes["num_attention_heads"],
        kv_rank=sizes["kv_lora_rank"], qk_nope=sizes["qk_nope_head_dim"],
        qk_rope=sizes["qk_rope_head_dim"], v_dim=sizes["v_head_dim"],
        rope_theta=sizes["rope_theta"], head_gate=False,
        rotary_interleaved=sizes["rope_interleave"],
        num_experts=sizes["num_experts"], top_k=sizes["num_experts_per_tok"],
        experts_held=tuple(range(sizes["num_experts_held"])),
        expert_dff=sizes["moe_intermediate_size"],
        shared_dff=sizes["n_shared_experts"] * sizes["moe_intermediate_size"],
        routed_scale=sizes["routed_scaling_factor"],
        groups=sizes["n_group"], groups_kept=sizes["topk_group"],
        eps=sizes["rms_norm_eps"], remat=True, head_chunks=max(2, seq // 1024),
        dtype=jnp.bfloat16,
        attention_fn=functools.partial(
            flash_attention, causal=True, block_q=block, block_k=block))
    apply_fn, loss_fn = make_lm_loss_fns(model)
    return {"apply_fn": apply_fn, "has_batch_stats": False, "model": model,
            "loss_fn": loss_fn}
