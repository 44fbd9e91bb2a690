"""Model FLOPs of a VGG-style CNN (3x3 'same' convolutions, a 2x2 max-pool
closing each stage, a dense head on the global average), from its shapes.

Counted are the convolutions and the head matmul, two FLOPs a
multiply-add; batch norm, ReLU and pooling are elementwise and left out.
Training counts each layer's forward, its weight gradient and its input
gradient, all the same size, except that no gradient flows into the
image: 3 x forward - the first convolution's forward.
"""
from __future__ import annotations


def conv_fwd_flops(hw: int, cin: int, cout: int, k: int = 3) -> float:
    """One k x k 'same' convolution on an hw x hw map, one sample."""
    return 2.0 * hw * hw * cin * cout * k * k


def vgg_layers(c: dict) -> list:
    """(hw, cin, cout) of every convolution, in order."""
    out, hw, cin = [], c["image_size"], c["in_channels"]
    for cout, n in c["stages"]:
        for _ in range(n):
            out.append((hw, cin, cout))
            cin = cout
        hw //= 2
    return out


def train_flops_per_sample(c: dict) -> float:
    convs = [conv_fwd_flops(*l) for l in vgg_layers(c)]
    head = 2.0 * c["stages"][-1][0] * c["n_classes"]
    return 3.0 * (sum(convs) + head) - convs[0]
