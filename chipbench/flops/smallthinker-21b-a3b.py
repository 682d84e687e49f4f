"""FLOPs the mixed-attention expert decoder's forward and backward passes
require per sequence, from shapes: 2 FLOPs per multiply-accumulate, backward
twice the forward, no recomputation.  Attention counts the visible
query-key pairs exactly (a kernel that computes whole tiles does more; that
is its cost, not the model's); the held experts count the expected
assignments a token under even routing, top_k x held / total; the head is
over the vocabulary slice; the embedding lookup costs none.

Also the operations and bytes of each attention kernel call, for its share
of the roofline (`layer_metrics/flash_*_roofline.py`)."""


def visible_pairs(seq, window=None):
    """Query-key pairs (i, j) with 0 <= i - j (< window)."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def windows(sizes):
    return [sizes["sliding_window_size"] if banded else None
            for banded in sizes["sliding_window_layout"][:sizes["num_hidden_layers"]]]


def forward_macs(sizes):
    """Multiply-accumulates of one forward pass of one sequence."""
    d, s = sizes["hidden_size"], sizes["seq_len"]
    inner = sizes["num_attention_heads"] * sizes["head_dim"]
    kv = sizes["num_key_value_heads"] * sizes["head_dim"]
    assignments = (sizes["moe_num_active_primary_experts"]
                   * sizes["moe_num_primary_experts_held"]
                   / sizes["moe_num_primary_experts"])
    per_token = (d * inner + 2 * d * kv + inner * d       # q, k, v, o
                 + d * sizes["moe_num_primary_experts"]   # router
                 + assignments * 3 * d * sizes["moe_ffn_hidden_size"])
    attention = sum(2 * visible_pairs(s, w) * inner for w in windows(sizes))
    return (sizes["num_hidden_layers"] * s * per_token + attention
            + s * d * sizes["vocab_size"])


def train_flops_per_sample(sizes):
    return 3 * 2 * forward_macs(sizes)


# matrix products per visible pair in each kernel, each 2 * head_dim FLOPs:
# forward q k^T and p v; dK/dV q k^T, g v^T, p^T g, ds^T q; dQ q k^T, g v^T, ds k
KERNEL_MATMULS = {"fwd": 2, "dkv": 4, "dq": 3}


def kernel_call(sizes, kernel, window, block=1024):
    """(FLOPs, HBM bytes) of one call of an attention kernel over the batch
    of one step: FLOPs of the visible pairs only; bytes of the blocks the
    grid fetches (bfloat16 operands, the row scalars left out): the outer
    block's operands and results once, the inner blocks once per outer
    block that touches them."""
    s, hd = sizes["seq_len"], sizes["head_dim"]
    bh = sizes["per_rank_batch"] * sizes["num_attention_heads"]
    flops = KERNEL_MATMULS[kernel] * 2 * hd * visible_pairs(s, window) * bh
    w = s if window is None else min(window, s)
    n = s // block
    # inner blocks touched per outer block: those the band crosses
    touched = sum(min(i, (w + block - 2) // block) + 1 for i in range(n))
    block_bytes = block * hd * 2
    outer = {"fwd": 2, "dkv": 4, "dq": 3}[kernel]   # q,o | k,v,dk,dv | q,g,dq
    inner = 2                                       # k,v | q,g | k,v
    return flops, bh * block_bytes * (outer * n + inner * touched)
