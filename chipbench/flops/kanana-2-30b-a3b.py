"""FLOPs the latent-attention expert decoder's forward and backward passes
require per sequence, from shapes: 2 FLOPs per multiply-accumulate, backward
twice the forward, **no recomputation** (the program recomputes every block in
its backward pass; that is its cost, not the model's, so `mfu_pct` stays a
share of model FLOPs).  Counted: every matrix product of the layers held
here, the head over the vocabulary slice, attention's visible query-key pairs
at a query-key head of 192 and a value head of 128 (a kernel that computes a
whole tile on the diagonal does more), the held experts at the expected
assignments a token under even routing, top_k x held / total, the shared
experts and the dense layer in full.  The embedding lookup, the norms and the
rotary cost none.

Also the operations and bytes of one call of each whole-sequence flash kernel
(`kernel_call`), for its share of the roofline
(`layer_metrics/flash_*_global_roofline.py`); how many calls a step makes is
counted from the trace, not here."""


def visible_pairs(seq):
    """Pairs (i, j) with j <= i."""
    return seq * (seq + 1) // 2


def forward_macs(sizes):
    """Multiply-accumulates of one forward pass of one sequence."""
    d, s, h = sizes["hidden_size"], sizes["seq_len"], sizes["num_attention_heads"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    dv, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    width = sizes["moe_intermediate_size"]
    assignments = (sizes["num_experts_per_tok"] * sizes["num_experts_held"]
                   / sizes["num_experts"])
    # q, the latent with the rotary key, every head's keys and values, o
    mixer = s * (d * h * qk + d * (rank + sizes["qk_rope_head_dim"])
                 + rank * h * (sizes["qk_nope_head_dim"] + dv) + h * dv * d)
    mixer += visible_pairs(s) * h * (qk + dv)
    dense = s * 3 * d * sizes["intermediate_size"]
    experts = s * (d * sizes["num_experts"] + assignments * 3 * d * width
                   + 3 * d * sizes["n_shared_experts"] * width)
    n, lead = sizes["num_hidden_layers"], sizes["first_k_dense_replace"]
    return s * d * sizes["vocab_size"] + n * mixer + lead * dense + (n - lead) * experts


def train_flops_per_sample(sizes):
    return 3 * 2 * forward_macs(sizes)


# matrix products a visible pair in each kernel, by the head size each sums or
# writes over (qk: the 192 query-key channels, v: the 128 value channels):
# forward q k^T and p v; dK/dV q k^T, dO v^T, p^T dO, dS^T q; dQ q k^T, dO v^T,
# dS k
PRODUCTS = {"fwd": (1, 1), "dkv": (2, 2), "dq": (2, 1)}


def kernel_call(sizes, kernel):
    """(FLOPs, HBM bytes) of one call of a whole-sequence flash kernel over
    the batch of one step, the same whatever implements it.  FLOPs of the
    visible pairs only.  Bytes of what the pass has to read and write once,
    bfloat16 but for the row scalars in float32: forward q, k, v in, o and the
    logsumexp out; the backward kernels q, k, v, dO, the logsumexp and the
    rows' dO . o in, dK and dV or dQ out."""
    b, s, h = sizes["per_rank_batch"], sizes["seq_len"], sizes["num_attention_heads"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    dv = sizes["v_head_dim"]
    n_qk, n_v = PRODUCTS[kernel]
    flops = 2 * (n_qk * qk + n_v * dv) * visible_pairs(s) * b * h
    rows = b * h * s
    narrow = {"fwd": 2 * qk + 2 * dv,       # q, k, v; o
              "dkv": 3 * qk + 3 * dv,       # q, k, v, dO; dK, dV
              "dq": 3 * qk + 2 * dv}[kernel]  # q, k, v, dO; dQ
    scalars = 1 if kernel == "fwd" else 2
    return flops, rows * (2 * narrow + 4 * scalars)
