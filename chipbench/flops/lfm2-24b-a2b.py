"""FLOPs the short-convolution expert decoder's forward and backward passes
require per sequence, from shapes: 2 FLOPs per multiply-accumulate, backward
twice the forward, **no recomputation** (the program recomputes every block in
its backward pass; that is its cost, not the model's, so `mfu_pct` stays a
share of model FLOPs).  Counted: every matrix product of the layers held
here, the tied head over the vocabulary slice, attention's visible query-key
pairs at heads of hidden / heads (a kernel that computes a whole tile on the
diagonal does more), the held experts at the expected assignments a token
under even routing, top_k x held / total, the dense layer in full, a short
convolution's taps and its two gates (a multiply-accumulate a tap and one a
gate: nothing beside its products).  The embedding lookup, the norms and the
rotary cost none.

Also the operations and bytes of one call of each short-convolution kernel
(`kernel_call`), for its share of the roofline
(`layer_metrics/short_conv_*_roofline.py`); how many calls a step makes is
counted from the trace, not here."""


def visible_pairs(seq):
    """Pairs (i, j) with j <= i."""
    return seq * (seq + 1) // 2


def layers(sizes):
    """(conv layers, attention layers, dense layers, expert layers) held."""
    held = sizes["published_layer_index"][:sizes["num_hidden_layers"]]
    conv = sum(sizes["layer_types"][i] == "conv" for i in held)
    dense = sum(i < sizes["num_dense_layers"] for i in held)
    return conv, len(held) - conv, dense, len(held) - dense


def forward_macs(sizes):
    """Multiply-accumulates of one forward pass of one sequence."""
    d, s, h = sizes["hidden_size"], sizes["seq_len"], sizes["num_attention_heads"]
    hd = d // h
    kv = sizes["num_key_value_heads"] * hd
    conv, attention, dense, expert = layers(sizes)
    assignments = (sizes["num_experts_per_tok"] * sizes["num_experts_held"]
                   / sizes["num_experts"])
    # in_proj and out_proj, the taps and the two gates
    conv_mixer = s * (d * 3 * d + d * d + (sizes["conv_L_cache"] + 2) * d)
    # q and o, k and v, the pairs' q k^T and p v
    attention_mixer = s * (2 * d * h * hd + 2 * d * kv) + visible_pairs(s) * h * 2 * hd
    dense_mlp = s * 3 * d * sizes["intermediate_size"]
    experts = s * (d * sizes["num_experts"]
                   + assignments * 3 * d * sizes["moe_intermediate_size"])
    return (s * d * sizes["vocab_size"] + conv * conv_mixer + attention * attention_mixer
            + dense * dense_mlp + expert * experts)


def train_flops_per_sample(sizes):
    return 3 * 2 * forward_macs(sizes)


def kernel_call(sizes, kernel):
    """(operations, HBM bytes) of one call of a short-convolution kernel over
    the batch of one step, the same whatever implements it.  Forward: a
    multiply a gate and a multiply-add a tap an element; B, C and x in, y out,
    in the product's two bytes.  Backward: the forward's convolution made
    again, the taps walked back, the three gates' products and the taps' own
    sums; dy, B, C and x in, dB, dC and dx out.  The taps and their gradient
    are a few rows.  Both are bound by the bytes by far."""
    rows = sizes["per_rank_batch"] * sizes["seq_len"]
    d, taps = sizes["hidden_size"], sizes["conv_L_cache"]
    elements = rows * d
    if kernel == "fwd":
        return elements * (2 + 2 * taps), elements * 2 * (3 + 1)
    # z, dconv, the convolution again, the walk back, dB, dC, dx, the taps' sums
    return elements * (2 + 2 * taps + 2 * taps + 3 + 2 * taps), elements * 2 * (4 + 3)
