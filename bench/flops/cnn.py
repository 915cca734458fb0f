"""Model FLOPs of the CNN family, counted from the configuration's shapes.

A convolution costs 2 * Ho * Wo * Cout * k * k * Cin / groups, a dense layer
2 * in * out; normalisation, activations and pooling are not counted. A
training step costs three forward passes (the forward, and the backward's
products for the inputs and for the weights), with no recompute.
"""
from __future__ import annotations


def _out(size: int, stride: int) -> int:
    return -(-size // stride)          # SAME padding


def forward_flops_per_example(config: dict) -> float:
    """MobileNetV2-style stack: stem conv 3x3/2, inverted residuals
    (expand 1x1, depthwise 3x3, project 1x1), head 1x1 conv, dense."""
    size = int(config["image_size"])
    cin = int(config["in_channels"])
    stem = int(config["stem_channels"])
    size = _out(size, 2)
    total = 2.0 * size * size * stem * 9 * cin
    c = stem
    for t, cout, n, s in config["inverted_residuals"]:
        for j in range(n):
            stride = s if j == 0 else 1
            hid = c * t
            if t != 1:
                total += 2.0 * size * size * hid * c
            size = _out(size, stride)
            total += 2.0 * size * size * hid * 9
            total += 2.0 * size * size * cout * hid
            c = cout
    head = int(config["head_channels"])
    total += 2.0 * size * size * head * c
    total += 2.0 * head * int(config["num_classes"])
    return total


def flops_per_round(config: dict, traffic: dict) -> float:
    fwd = forward_flops_per_example(config)
    train = (3.0 * fwd * int(traffic["clients"]) * int(traffic["batch"])
             * int(traffic["local_steps"]))
    evals = fwd * int(traffic["test_examples"]) if traffic["eval_every_round"] \
        else 0.0
    return train + evals
