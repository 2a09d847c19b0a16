"""Operations a model REQUIRES, from its shapes — never XLA's count of the
program it happened to build.

A training step needs the forward pass once and the backward pass twice
over (one product for the input's gradient, one for the weight's): 3 x 2 x
multiply-adds of every convolution and dense layer. Nothing recomputed is
counted. BatchNorm, activations, pooling and the optimizer are left out: they
are bandwidth, not FLOPs, and ``kernels.other_ms`` carries them.

A family's reference answers ``train_flops_per_item(cfg)`` from the helpers
here: convolution layers by ``layer_macs``; a token family by the four
per-TOKEN counts below, where a token is the item. What one chip of a stated
deployment does not hold (experts, vocabulary rows) it does not compute, and
is not counted.
"""


def layer_macs(layer):
    """Multiply-adds of one conv/dense layer for ONE item (forward)."""
    return (layer["k"] ** 2 * layer["cin"] * layer["cout"]
            * layer["out_hw"] ** 2)


def forward_macs(layers):
    return sum(layer_macs(l) for l in layers)


def train_flops(forward_macs_per_item):
    """2 FLOPs per multiply-add, x 3 for forward + two backward products."""
    return 6 * forward_macs_per_item


def train_flops_per_item(layers):
    return train_flops(forward_macs(layers))


# ------------------------------------------------- per token (forward MACs)
def dense_macs(d_in, d_out):
    """One token through a (d_in -> d_out) projection."""
    return d_in * d_out


def causal_attention_macs(seq_len, heads, qk_dim, v_dim):
    """Scores and their product with the values for one token of a causal
    sequence, averaged over its positions: position t (from 1) sees t keys,
    (seq_len + 1) / 2 on average: half the square, not the square. Documents
    packed into one row with no mask at their boundaries attend across them,
    so the row's length is the sequence length."""
    return heads * (qk_dim + v_dim) * (seq_len + 1) / 2


def expert_layer_macs(macs_per_expert, experts_per_token, share_held,
                      shared_experts=0):
    """One token through a layer of routed experts of which this chip holds
    ``share_held`` (experts held / experts): it sees the token in
    ``experts_per_token * share_held`` of its experts on average, and in
    every shared expert."""
    return macs_per_expert * (experts_per_token * share_held + shared_experts)


def head_macs(width, vocab_held):
    """One token against the ``vocab_held`` rows of the vocabulary head that
    this chip holds. An embedding lookup is a gather: no multiply-adds."""
    return width * vocab_held
