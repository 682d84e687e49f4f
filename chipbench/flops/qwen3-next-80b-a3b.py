"""FLOPs the gated-delta-rule expert decoder's forward and backward passes
require per sequence, from shapes: 2 FLOPs per multiply-accumulate, backward
twice the forward, **no recomputation** (the program recomputes every block in
its backward pass; that is its cost, not the model's, so `mfu_pct` stays a
share of model FLOPs).  Counted: every matrix product of the layers held here,
the untied head over the vocabulary slice, attention's visible query-key pairs
at heads of `head_dim` with q's product twice as wide (the gate), the delta
rule's products in its chunked form at the configuration's `gdn_chunk_size`
over a chunk's visible pairs only (q k^T and k k^T once a key head, the rest a
value head; a kernel that computes a chunk's whole square does more), the held
experts at the expected assignments a token under even routing, top_k x held /
total, the shared expert and its gate.  The embedding lookup, the convolution's
four taps a channel, the norms, the gates, the decay matrix, the rotary and the
chunk's triangular substitution cost none.

Also the operations and bytes of one call of each of the delta rule's four
kernels (`kernel_call`), for its share of the roofline
(`layer_metrics/gdn_*_roofline.py`); how many calls a step makes is counted
from the trace, not here."""

# value heads a call of the kernels walks: the kernels' own constant
from bluefog_tpu.kernels.gdn import HEADS_A_CALL


def kinds(sizes):
    held = sizes["published_layer_index"][:sizes["num_hidden_layers"]]
    return ["attention" if (i + 1) % sizes["full_attention_interval"] == 0 else "gdn"
            for i in held]


def visible_pairs(seq):
    """Pairs (i, j) with j <= i."""
    return seq * (seq + 1) // 2


def _delta_dims(sizes):
    return (sizes["gdn_chunk_size"], sizes["linear_key_head_dim"],
            sizes["linear_value_head_dim"],
            sizes["linear_num_value_heads"] // sizes["linear_num_key_heads"])


def kernel_macs(sizes, kernel):
    """Multiply-accumulates a chunk of `HEADS_A_CALL` value heads in a kernel.
    The walk's, a value head (as Ling's): W S, (q exp G) S and the state's
    update (chunk x K x V each) and P U over the visible pairs forward; seven
    chunk x K x V and P's two over the visible pairs backward.  The stage's: q
    k^T and k k^T (below the diagonal) once a key head, T (k exp G) and T v a
    value head, over the visible pairs; backward those again, dX's two terms,
    X^T dW and X^T dU0, the two products through X, and the pairs' four
    cotangent products."""
    c, k, v, share = _delta_dims(sizes)
    vis, heads, key_heads = visible_pairs(c), HEADS_A_CALL, HEADS_A_CALL // share
    pairs = key_heads * (2 * vis - c) * k
    return {
        "chunk_fwd": heads * (3 * c * k * v + vis * v),
        "chunk_bwd": heads * (7 * c * k * v + 2 * vis * v),
        "intra_fwd": pairs + heads * vis * (k + v),
        "intra_bwd": 2 * pairs + heads * (3 * vis * (k + v) + 2 * vis * c
                                          + 2 * (2 * vis - c) * k),
    }[kernel]


def gdn_macs(sizes):
    """Multiply-accumulates of one linear layer's delta rule over one sequence,
    chunked: the stage's and the walk's forward kernels over all the heads."""
    chunks = -(-sizes["seq_len"] // sizes["gdn_chunk_size"])
    calls = sizes["linear_num_value_heads"] // HEADS_A_CALL
    return chunks * calls * (kernel_macs(sizes, "intra_fwd")
                             + kernel_macs(sizes, "chunk_fwd"))


def forward_macs(sizes):
    """Multiply-accumulates of one forward pass of one sequence."""
    d, s = sizes["hidden_size"], sizes["seq_len"]
    n_h, n_kv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                     sizes["head_dim"])
    n_v = sizes["linear_num_value_heads"]
    keys = sizes["linear_num_key_heads"] * sizes["linear_key_head_dim"]
    values = n_v * sizes["linear_value_head_dim"]
    assignments = (sizes["num_experts_per_tok"] * sizes["num_experts_held"]
                   / sizes["num_experts"])
    # the router, the held experts, the shared expert and its gate
    experts = s * (d * sizes["num_experts"]
                   + assignments * 3 * d * sizes["moe_intermediate_size"]
                   + 3 * d * sizes["shared_expert_intermediate_size"] + d)
    total = s * d * sizes["vocab_size"]
    for kind in kinds(sizes):
        if kind == "gdn":   # [q, k, v, z], [b, a], o; the delta rule
            total += s * (d * (2 * keys + 2 * values) + d * 2 * n_v + values * d)
            total += gdn_macs(sizes)
        else:               # [q, gate] and o, k and v, the pairs' q k^T and p v
            total += s * (3 * d * n_h * hd + 2 * d * n_kv * hd)
            total += visible_pairs(s) * n_h * 2 * hd
        total += experts
    return total


def train_flops_per_sample(sizes):
    return 3 * 2 * forward_macs(sizes)


def kernel_call(sizes, kernel):
    """(FLOPs, HBM bytes) of one call of a delta-rule kernel: `HEADS_A_CALL`
    value heads (and the key heads that serve them) over the batch of one step,
    the same whatever implements it.  Bytes of what the pass has to read and
    write once.  The walk (as Ling's): q exp G, W, k exp(G[last] - G), the
    output and their cotangents in bfloat16; U0, P, the chunk's decay and their
    cotangents in float32; into the backward pass a float32 state a head a
    chunk.  The stage forward: q and k of the key heads and v in bfloat16, g
    and beta a number a head in float32, in; the walk's six out.  Backward:
    those and the six cotangents in; dq, dk, dv in bfloat16 and dg, dbeta out.
    All four are bound by the bytes as counted."""
    b, s = sizes["per_rank_batch"], sizes["seq_len"]
    c, k, v, share = _delta_dims(sizes)
    heads, key_heads = HEADS_A_CALL, HEADS_A_CALL // share
    steps = b * -(-s // c)
    flops = 2 * kernel_macs(sizes, kernel) * steps
    narrow, wide = c * k * 2, c * v * 4 + c * c * 4 + k * 4   # a value head's
    walked = heads * (3 * narrow + wide)                       # what the stage hands over
    read = 2 * key_heads * c * k * 2 + heads * c * v * 2 + 2 * heads * c * 4
    if kernel == "chunk_fwd":   # the six in; o out
        return flops, steps * (walked + heads * c * v * 2)
    if kernel == "chunk_bwd":   # the six, dO and the state in; six cotangents out
        return flops, steps * (2 * walked + heads * (c * v * 2 + k * v * 4))
    if kernel == "intra_fwd":
        return flops, steps * (read + walked)
    return flops, steps * (2 * read + walked)                  # intra_bwd
