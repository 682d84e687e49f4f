"""FLOPs the delta-rule / latent-attention expert decoder's forward and
backward passes require per sequence, from shapes: 2 FLOPs per
multiply-accumulate, backward twice the forward, **no recomputation** (the
program recomputes every block in its backward pass; that is its cost, not the
model's, so `mfu_pct` stays a share of model FLOPs).  Counted: every matrix
product of the layers held here, the head over the vocabulary slice, the
latent-attention layer's visible query-key pairs at a query-key head of 192
and a value head of 128, the delta rule's products in its chunked form at the
configuration's `kda_chunk_size`, the visible pairs of a chunk only (a kernel
that computes a chunk's whole square does more), the held experts at the
expected assignments a token under even routing, top_k x held / total.  The
embedding lookup, the convolution's four taps a channel, the norms, the gates,
the rotary and the chunk's triangular substitution (a fiftieth of the chunk's
products) cost none.

Also the operations and bytes of one call of each delta-rule kernel, for its
share of the roofline (`layer_metrics/kda_chunk_*_roofline.py`); how many
calls a step makes is counted from the trace, not here."""

# heads a call of the kernels walks (`kernels.kda.kda_chunked`'s
# `heads_at_once`; the program passes none)
HEADS_A_CALL = 4


def kinds(sizes):
    n = sizes["num_hidden_layers"]
    return list(zip(sizes["layer_types"][:n],
                    (k == "dense" for k in sizes["mlp_layer_types"][:n])))


def visible_pairs(seq):
    """Pairs (i, j) with j <= i."""
    return seq * (seq + 1) // 2


def kernel_macs(sizes, kernel):
    """Multiply-accumulates a head a chunk in a delta-rule kernel, K = V =
    head_dim.  Forward: W S, (q exp G) S and the state's update (chunk x K x V
    each), P U over the visible pairs.  Backward: U again, the cotangents of q
    exp G, W and k exp(G[last] - G), the two terms of the state's and K's
    term of U's (chunk x K x V each: seven), P's and P^T dO over the visible
    pairs."""
    c, k = sizes["kda_chunk_size"], sizes["head_dim"]
    return {"fwd": 3 * c * k * k + visible_pairs(c) * k,
            "bwd": 7 * c * k * k + 2 * visible_pairs(c) * k}[kernel]


def kda_macs(sizes):
    """Multiply-accumulates of one linear layer's delta rule over one
    sequence, chunked: a head a chunk, the pairs A (below the diagonal) and P,
    T (k exp G) and T v over the visible pairs, and the forward kernel's."""
    c, s = sizes["kda_chunk_size"], sizes["seq_len"]
    h, k = sizes["num_attention_heads"], sizes["head_dim"]
    vis = visible_pairs(c)
    return -(-s // c) * h * ((vis - c) * k + 3 * vis * k + kernel_macs(sizes, "fwd"))


def forward_macs(sizes):
    """Multiply-accumulates of one forward pass of one sequence."""
    d, s = sizes["hidden_size"], sizes["seq_len"]
    h, hd = sizes["num_attention_heads"], sizes["head_dim"]
    inner = h * hd
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    nv = sizes["qk_nope_head_dim"] + sizes["v_head_dim"]
    rank = sizes["kv_lora_rank"]
    assignments = (sizes["num_experts_per_tok"] * sizes["num_experts_held"]
                   / sizes["num_experts"])
    total = s * d * sizes["vocab_size"]
    for kind, dense in kinds(sizes):
        if kind == "kda":   # q, k, v, f, g, o and the step a head; the delta rule
            total += s * (6 * d * inner + d * h) + kda_macs(sizes)
        else:               # q, the latent and the rotary key, k and v, gate, o
            total += s * (d * h * qk + d * (rank + sizes["qk_rope_head_dim"])
                          + rank * h * nv + d * h + h * sizes["v_head_dim"] * d)
            total += visible_pairs(s) * h * (qk + sizes["v_head_dim"])
        if dense:
            total += s * 3 * d * sizes["intermediate_size"]
        else:
            total += s * (d * sizes["num_experts"]
                          + assignments * 3 * d * sizes["moe_intermediate_size"]
                          + 3 * d * sizes["moe_shared_expert_intermediate_size"])
    return total


def train_flops_per_sample(sizes):
    return 3 * 2 * forward_macs(sizes)


def kernel_call(sizes, kernel):
    """(FLOPs, HBM bytes) of one call of a delta-rule kernel: `HEADS_A_CALL`
    heads over the batch of one step, the same whatever implements it.  Bytes
    of what the pass has to read and write once: q exp G, W, k exp(G[last] -
    G), the output and their cotangents in bfloat16; U0, P, the chunk's decay
    and their cotangents in float32; into the backward pass a float32 state a
    head a chunk."""
    b, s = sizes["per_rank_batch"], sizes["seq_len"]
    c, k = sizes["kda_chunk_size"], sizes["head_dim"]
    steps = b * HEADS_A_CALL * -(-s // c)
    flops = 2 * kernel_macs(sizes, kernel) * steps
    narrow, wide = c * k * 2, c * k * 4 + c * c * 4 + k * 4
    if kernel == "fwd":   # q exp G, W, k exp(.) and U0, P, the decay in; o out
        return flops, steps * (4 * narrow + wide)
    # the same and dO and the state in; six cotangents out
    return flops, steps * (7 * narrow + 2 * wide + k * k * 4)
