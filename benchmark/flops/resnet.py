"""Operations of one ResNet v1 (bottleneck) image, from the shapes alone.

A multiply-add counts as two operations. Convolutions and the classifier
only: batch norm, ReLU, pooling and the loss are not matrix work and are left
out, as in He et al.'s own count (3.8e9 multiply-adds for the 50-layer net).
"""
from __future__ import annotations


def _out(size, kernel, stride, pad):
    return (size + 2 * pad - kernel) // stride + 1


def forward_flops(config, traffic=None):
    m = config["published"]
    ch = m["channels"]
    hw = _out(m["image"], 7, 2, 3)
    total = 2 * 7 * 7 * 3 * ch[0] * hw * hw
    hw = _out(hw, 3, 2, 1)  # max pool
    cin = ch[0]
    for s, (n, cout) in enumerate(zip(m["layers"], ch[1:]), start=1):
        mid = cout // 4
        for b in range(n):
            stride = 2 if (b == 0 and s > 1) else 1
            out = _out(hw, 1, stride, 0)  # v1: the stride sits in the first 1x1
            total += 2 * cin * mid * out * out
            total += 2 * 3 * 3 * mid * mid * out * out
            total += 2 * mid * cout * out * out
            if b == 0:
                total += 2 * cin * cout * out * out
            hw, cin = out, cout
    return total + 2 * cin * m["classes"]


def train_flops_per_sample(config, traffic=None):
    """Forward, and backward at twice the forward: the model's operations,
    recomputation not counted."""
    return 3 * forward_flops(config, traffic)
