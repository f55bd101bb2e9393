"""Work counts of the dwfl-paper MLP family, from the configuration's sizes.

Counted on the unpadded shapes the algorithm needs, so a change to the
program's padding or tiling moves a roofline share and not this yardstick.
"""
from __future__ import annotations


def layer_shapes(model):
    dims = ([model["input_dim"]] + [model["hidden"]] * model["hidden_layers"]
            + [model["classes"]])
    return list(zip(dims[:-1], dims[1:]))


def params_per_worker(model):
    """Length d of one worker's flat parameter row (weights and biases)."""
    return sum(i * o + o for i, o in layer_shapes(model))


def train_flops_per_sample(model):
    """Forward 2P, weight gradients 2P, input gradients 2(P - first layer):
    P matmul weights; the first layer needs no input gradient."""
    shapes = layer_shapes(model)
    P = sum(i * o for i, o in shapes)
    first = shapes[0][0] * shapes[0][1]
    return 2 * P + 2 * P + 2 * (P - first)


def round_flops(cfg):
    """Model FLOPs of one DWFL round: every worker's batch, forward and
    backward."""
    pr = cfg["protocol"]
    return pr["workers"] * pr["batch_size"] * train_flops_per_sample(
        cfg["model"])


def dp_mix_work(cfg):
    """What one dense dp_mix round needs on [N, d] f32: FLOPs 2 N^2 d (the
    mix) and bytes (3 N d + N^2) * 4 (params and gradients read, the new
    buffer written, W read)."""
    N = cfg["protocol"]["workers"]
    d = params_per_worker(cfg["model"])
    return 2 * N * N * d, (3 * N * d + N * N) * 4
