"""FLOPs the ResNet-50 forward and backward passes require per image, from
shapes: 2 FLOPs per multiply-accumulate of every convolution and of the head,
backward twice the forward, no recomputation.  Element-wise work (batch norm,
ReLU, pooling) is not counted, as is usual for a model utilization."""


def forward_macs(sizes):
    """Multiply-accumulates of one forward pass of one image."""
    exp = sizes["bottleneck_expansion"]
    hw = -(-sizes["image_size"] // 2)           # 7x7 stride 2, SAME
    c = sizes["num_filters"]
    macs = hw * hw * 7 * 7 * sizes["channels"] * c
    hw = -(-hw // 2)                             # 3x3 max pool stride 2
    for i, count in enumerate(sizes["stage_sizes"]):
        f = sizes["num_filters"] * 2 ** i
        for j in range(count):
            stride = 2 if i > 0 and j == 0 else 1
            out = -(-hw // stride)
            macs += hw * hw * c * f              # 1x1 reduce, input resolution
            macs += out * out * 9 * f * f        # 3x3, carries the stride
            macs += out * out * f * f * exp      # 1x1 expand
            if j == 0:
                macs += out * out * c * f * exp  # projection shortcut
            hw, c = out, f * exp
    return macs + c * sizes["num_classes"]


def train_flops_per_sample(sizes):
    return 3 * 2 * forward_macs(sizes)
