"""Operations a model REQUIRES, from its shapes — never XLA's count of the
program it happened to build.

A training step needs the forward pass once and the backward pass twice
over (one product for the input's gradient, one for the weight's): 3 x 2 x
multiply-adds of every convolution and dense layer. Nothing recomputed is
counted. BatchNorm, activations, pooling and the optimizer are left out: they
are bandwidth, not FLOPs, and ``kernels.other_ms`` carries them.
"""


def layer_macs(layer):
    """Multiply-adds of one conv/dense layer for ONE item (forward)."""
    return (layer["k"] ** 2 * layer["cin"] * layer["cout"]
            * layer["out_hw"] ** 2)


def forward_macs(layers):
    return sum(layer_macs(l) for l in layers)


def train_flops_per_item(layers):
    """2 FLOPs per multiply-add, x 3 for forward + two backward products."""
    return 6 * forward_macs(layers)
