"""FLOPs the Laguna decoder's forward and backward passes require per
sequence, from shapes: 2 FLOPs per multiply-accumulate, backward twice the
forward, no recomputation.  Attention counts the visible query-key pairs
exactly, at the head count of the layer (a kernel that computes whole tiles
does more; that is its cost, not the model's); the held experts count the
expected assignments a token under even routing, top_k x held / total; the
shared expert and the dense layer are counted in full; the head is over the
vocabulary slice; the embedding lookup and the rotary cost none.

Also the operations and bytes of each attention kernel call, for its share of
the roofline (`layer_metrics/flash_*_roofline.py`), with the head count of the
layer the kernel runs in and the block the program passes."""

import functools
import os


def visible_pairs(seq, window=None):
    """Query-key pairs (i, j) with 0 <= i - j (< window)."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layers(sizes):
    """(query heads, window, dense) a layer held here."""
    return [(sizes["num_attention_heads_per_layer"][l],
             sizes["sliding_window"] if sizes["layer_types"][l] == "sliding_attention"
             else None,
             sizes["mlp_layer_types"][l] == "dense")
            for l in range(sizes["num_hidden_layers"])]


def windows(sizes):
    return [window for _, window, _ in layers(sizes)]


def forward_macs(sizes):
    """Multiply-accumulates of one forward pass of one sequence."""
    d, s, hd = sizes["hidden_size"], sizes["seq_len"], sizes["head_dim"]
    kv = sizes["num_key_value_heads"] * hd
    assignments = (sizes["num_experts_per_tok"] * sizes["num_experts_held"]
                   / sizes["num_experts"])
    total = s * d * sizes["vocab_size"]
    for heads, window, dense in layers(sizes):
        inner = heads * hd
        per_token = d * inner + 2 * d * kv + d * heads + inner * d  # q, k, v, gate, o
        if dense:
            per_token += 3 * d * sizes["intermediate_size"]
        else:
            per_token += (d * sizes["num_experts"]                 # router
                          + assignments * 3 * d * sizes["moe_intermediate_size"]
                          + 3 * d * sizes["shared_expert_intermediate_size"])
        total += s * per_token + 2 * visible_pairs(s, window) * inner
    return total


def train_flops_per_sample(sizes):
    return 3 * 2 * forward_macs(sizes)


# matrix products per visible pair in each kernel, each 2 * head_dim FLOPs:
# forward q k^T and p v; dK/dV q k^T, g v^T, p^T g, ds^T q; dQ q k^T, g v^T, ds k
KERNEL_MATMULS = {"fwd": 2, "dkv": 4, "dq": 3}


@functools.lru_cache(maxsize=None)
def program_blocks():
    """{layer kind: (query rows, keys)} of the tile the program passes."""
    from chipbench import manifest
    return manifest.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), os.pardir, "program",
        "laguna-xs.2.py")).BLOCKS


def kernel_call(sizes, kernel, window, block=None):
    """(FLOPs, HBM bytes) of one call of an attention kernel over the batch of
    one step, in a layer of the kind `window` names: FLOPs of the visible
    pairs only; bytes of the blocks the grid fetches (bfloat16 operands, the
    row scalars left out), the shared key-value heads read where they lie:
    the outer block's operands and results once, the inner blocks once per
    outer block that touches them."""
    s, hd, b = sizes["seq_len"], sizes["head_dim"], sizes["per_rank_batch"]
    heads = next(h for h, w, _ in layers(sizes) if w == window)
    kv = sizes["num_key_value_heads"]
    flops = KERNEL_MATMULS[kernel] * 2 * hd * visible_pairs(s, window) * b * heads
    bq, bk = block or program_blocks()[
        "full_attention" if window is None else "sliding_attention"]
    w = s if window is None else min(window, s)
    nq, nk = s // bq, s // bk
    # key blocks a query block touches (rows [i bq - w + 1, (i + 1) bq - 1]),
    # and query blocks that touch a key block: the same tiles, counted both ways
    tiles = sum(((i + 1) * bq - 1) // bk - max(i * bq - w + 1, 0) // bk + 1
                for i in range(nq))
    row, key = bq * hd * 2, bk * hd * 2   # bytes of a block of rows, of keys
    if kernel == "fwd":    # q, o once a head; k, v a tile
        nbytes = heads * (2 * nq * row + 2 * tiles * key)
    elif kernel == "dq":   # q, g, dq once a head; k, v a tile
        nbytes = heads * (3 * nq * row + 2 * tiles * key)
    else:                  # k, v, dk, dv once a shared head; q, g a tile and head
        nbytes = kv * 4 * nk * key + heads * 2 * tiles * row
    return flops, b * nbytes
