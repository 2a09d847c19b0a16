"""Builder of the tests' token model: embedding -> gated-SiLU dense layers,
each added to its input -> an untied vocabulary head. No normalisation, no
attention, no bias: the least that takes ids and gives logits per token."""
from mxnet_tpu import gluon
from mxnet_tpu.gluon import nn


class GatedMLPLM(gluon.HybridBlock):
    def __init__(self, vocab, width, hidden, layers, **kw):
        super().__init__(**kw)
        dense = lambda out, inp: nn.Dense(  # noqa: E731
            out, use_bias=False, flatten=False, in_units=inp)
        with self.name_scope():
            self.embed = nn.Embedding(vocab, width)
            self.mlps = []
            for i in range(layers):
                mlp = dense(hidden, width), dense(hidden, width), dense(width, hidden)
                for name, block in zip(("gate", "up", "down"), mlp):
                    self.register_child(block, "%s%d" % (name, i))
                self.mlps.append(mlp)
            self.head = dense(vocab, width)

    def hybrid_forward(self, F, x):
        h = self.embed(x)
        for gate, up, down in self.mlps:
            a = gate(h)
            h = h + down(a * F.sigmoid(a) * up(h))
        return self.head(h)


def build(**kw):
    return GatedMLPLM(**kw)
