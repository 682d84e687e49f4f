"""FLOPs the BERT encoder's forward and backward passes require per sequence,
from shapes: 2 FLOPs per multiply-accumulate of every matmul (projections,
attention scores and values, feed-forward, pooler, classifier), backward
twice the forward, no recomputation.  The embedding lookup costs none."""


def forward_macs(sizes):
    """Multiply-accumulates of one forward pass of one sequence."""
    d, ff, s = sizes["hidden_size"], sizes["intermediate_size"], sizes["seq_len"]
    inner = sizes["num_attention_heads"] * sizes["head_dim"]
    per_token = (3 * d * inner      # q, k, v projections
                 + 2 * s * inner    # scores and weighted values
                 + inner * d        # output projection
                 + 2 * d * ff)      # feed-forward
    return (sizes["num_hidden_layers"] * s * per_token
            + d * d + d * sizes["num_labels"])


def train_flops_per_sample(sizes):
    return 3 * 2 * forward_macs(sizes)
