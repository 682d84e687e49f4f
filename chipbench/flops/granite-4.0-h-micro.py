"""FLOPs the hybrid state-space decoder's forward and backward passes require
per sequence, from shapes: 2 FLOPs per multiply-accumulate, backward twice the
forward, **no recomputation** (the program recomputes every block in its
backward pass; that is its cost, not the model's, so `mfu_pct` stays a share
of model FLOPs).  Counted: every matrix product of the layers held here, the
head over the vocabulary slice, attention's visible query-key pairs, and the
scan's products in its chunked form at the configuration's `mamba_chunk_size`,
the visible pairs of a chunk only (a kernel that computes a chunk's whole
square does more).  The embedding lookup, the convolution's four taps a
channel, the norms and the gates cost none.

Also the operations and bytes of one call of each scan kernel, for its share
of the roofline (`layer_metrics/ssd_chunk_*_roofline.py`), and how many calls
of it a step makes."""


def kinds(sizes):
    return sizes["layer_types"][:sizes["num_hidden_layers"]]


def visible_pairs(seq):
    """Query-key pairs (i, j) with j <= i."""
    return seq * (seq + 1) // 2


def scan_macs(sizes):
    """Multiply-accumulates of one state-space layer's scan over one
    sequence, chunked: a head a chunk, (L * C B^T)(dt * X) over the visible
    pairs, C S_in^T and the chunk's state (chunk x state x channels each); a
    group a chunk, C B^T over the visible pairs."""
    q, s = sizes["mamba_chunk_size"], sizes["seq_len"]
    h, p = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    n, g = sizes["mamba_d_state"], sizes["mamba_n_groups"]
    chunks = -(-s // q)
    return chunks * (h * (visible_pairs(q) * p + 2 * q * n * p)
                     + g * visible_pairs(q) * n)


def forward_macs(sizes):
    """Multiply-accumulates of one forward pass of one sequence."""
    d, s, f = sizes["hidden_size"], sizes["seq_len"], sizes["intermediate_size"]
    att = sizes["num_attention_heads"] * sizes["attention_head_dim"]
    kv = sizes["num_key_value_heads"] * sizes["attention_head_dim"]
    h, n, g = sizes["mamba_n_heads"], sizes["mamba_d_state"], sizes["mamba_n_groups"]
    inner = h * sizes["mamba_d_head"]
    total = s * d * sizes["vocab_size"]
    for kind in kinds(sizes):
        if kind == "attention":
            total += s * (d * att + 2 * d * kv + att * d) + 2 * visible_pairs(s) * att
        else:  # in_proj to [z, x, B, C, dt], out_proj, the scan
            total += s * (d * (2 * inner + 2 * g * n + h) + inner * d) + scan_macs(sizes)
        total += s * 3 * d * f
    return total


def train_flops_per_sample(sizes):
    return 3 * 2 * forward_macs(sizes)


def kernel_calls_per_step(sizes, kernel):
    """A state-space layer calls the forward kernel twice a step (the forward
    pass, and again where the backward pass recomputes its block) and the
    backward kernel once."""
    return kinds(sizes).count("mamba") * {"fwd": 2, "bwd": 1}[kernel]


def kernel_call(sizes, kernel):
    """(FLOPs, HBM bytes) of one call of a scan kernel over the batch of one
    step, the same whatever implements it: the forward's products as
    `scan_macs` counts them and twice that backward; bytes of what the pass
    has to read and write once, operands and cotangents in bfloat16 (x, y and
    theirs; B, C and theirs), the step sizes and theirs in float32, and, into
    the backward pass, a float32 state a head a chunk."""
    b, s = sizes["per_rank_batch"], sizes["seq_len"]
    h, p = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    n, g = sizes["mamba_d_state"], sizes["mamba_n_groups"]
    chunks = -(-s // sizes["mamba_chunk_size"])
    flops = 2 * scan_macs(sizes) * b
    x, bc, dt = s * h * p * 2, s * g * n * 2, s * h * 4
    if kernel == "fwd":   # x, B, C, dt in; y out
        return flops, b * (2 * x + 2 * bc + dt)
    # x, dy, B, C, dt, the states in; dx, dB, dC, d dt out
    return 2 * flops, b * (3 * x + 4 * bc + 2 * dt + chunks * h * p * n * 4)
