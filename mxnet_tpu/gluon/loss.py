"""Gluon losses (reference: ``python/mxnet/gluon/loss.py``)."""
from __future__ import annotations

import numpy as np

from .block import HybridBlock

__all__ = ["Loss", "L2Loss", "L1Loss", "SigmoidBinaryCrossEntropyLoss",
           "SigmoidBCELoss", "SoftmaxCrossEntropyLoss", "SoftmaxCELoss",
           "KLDivLoss", "CTCLoss", "HuberLoss", "HingeLoss", "SquaredHingeLoss",
           "LogisticLoss", "TripletLoss", "CosineEmbeddingLoss",
           "ExpectedExitCELoss", "TiedHeadCELoss"]


def _apply_weighting(F, loss, weight=None, sample_weight=None):
    if sample_weight is not None:
        loss = F.broadcast_mul(loss, sample_weight)
    if weight is not None:
        loss = loss * weight
    return loss


def _reshape_like(F, x, y):
    return F.reshape_like(x, y)


class Loss(HybridBlock):
    def __init__(self, weight, batch_axis, **kwargs):
        super().__init__(**kwargs)
        self._weight = weight
        self._batch_axis = batch_axis

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError


class L2Loss(Loss):
    def __init__(self, weight=1.0, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, label, pred)
        loss = F.square(label - pred)
        loss = _apply_weighting(F, loss, self._weight / 2, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class L1Loss(Loss):
    def __init__(self, weight=1.0, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, label, pred)
        loss = F.abs(label - pred)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class SigmoidBinaryCrossEntropyLoss(Loss):
    def __init__(self, from_sigmoid=False, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_sigmoid = from_sigmoid

    def hybrid_forward(self, F, pred, label, sample_weight=None, pos_weight=None):
        label = _reshape_like(F, label, pred)
        if not self._from_sigmoid:
            # log(1+exp(-|x|)) + max(x,0) - x*z  (numerically stable)
            loss = F.relu(pred) - pred * label + F.Activation(
                -F.abs(pred), act_type="softrelu")
        else:
            eps = 1e-12
            loss = -(F.log(pred + eps) * label + F.log(1 - pred + eps) * (1 - label))
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


SigmoidBCELoss = SigmoidBinaryCrossEntropyLoss


class SoftmaxCrossEntropyLoss(Loss):
    def __init__(self, axis=-1, sparse_label=True, from_logits=False, weight=None,
                 batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._axis = axis
        self._sparse_label = sparse_label
        self._from_logits = from_logits

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        if not self._from_logits:
            pred = F.log_softmax(pred, axis=self._axis)
        if self._sparse_label:
            loss = -F.pick(pred, label, axis=self._axis, keepdims=True)
        else:
            label = _reshape_like(F, label, pred)
            loss = -F.sum(pred * label, axis=self._axis, keepdims=True)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


SoftmaxCELoss = SoftmaxCrossEntropyLoss


class KLDivLoss(Loss):
    def __init__(self, from_logits=True, axis=-1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_logits = from_logits
        self._axis = axis

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        if not self._from_logits:
            pred = F.log_softmax(pred, axis=self._axis)
        loss = label * (F.log(label + 1e-12) - pred)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class CTCLoss(Loss):
    """Connectionist temporal classification (reference gluon/loss.py:CTCLoss
    over src/operator/contrib/ctc_loss.cc; here the registered ``CTCLoss`` op
    provides the log-domain DP as XLA while-loops)."""

    def __init__(self, layout="NTC", label_layout="NT", weight=None, **kwargs):
        super().__init__(weight, 0, **kwargs)
        self._layout = layout
        self._label_layout = label_layout

    def hybrid_forward(self, F, pred, label, pred_lengths=None, label_lengths=None,
                       sample_weight=None):
        # one route, imperative or symbolic: the registered CTCLoss op (TNC
        # layout, gluon blank-last convention, -1 label padding)
        p = pred if self._layout == "TNC" else F.transpose(pred,
                                                           axes=(1, 0, 2))
        lab = label if self._label_layout == "NT" else F.transpose(
            label, axes=(1, 0))
        # the op's positional arg list is fixed; unused length slots get
        # zero placeholders the kernel ignores (use_*_lengths=False)
        pl = pred_lengths if pred_lengths is not None else F.zeros((1,))
        ll = label_lengths if label_lengths is not None else F.zeros((1,))
        return F.CTCLoss(p, lab, pl, ll, blank_label="last",
                         use_data_lengths=pred_lengths is not None,
                         use_label_lengths=label_lengths is not None)


class HuberLoss(Loss):
    def __init__(self, rho=1.0, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._rho = rho

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, label, pred)
        loss = F.abs(label - pred)
        loss = F.where(loss > self._rho,
                       loss - 0.5 * self._rho,
                       (0.5 / self._rho) * F.square(loss))
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class HingeLoss(Loss):
    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, label, pred)
        loss = F.relu(self._margin - pred * label)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class SquaredHingeLoss(Loss):
    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, label, pred)
        loss = F.square(F.relu(self._margin - pred * label))
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class LogisticLoss(Loss):
    def __init__(self, weight=None, batch_axis=0, label_format="signed", **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._label_format = label_format

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        label = _reshape_like(F, label, pred)
        if self._label_format == "signed":
            label = (label + 1.0) / 2.0
        loss = F.relu(pred) - pred * label + F.Activation(
            -F.abs(pred), act_type="softrelu")
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class TripletLoss(Loss):
    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, pred, positive, negative, sample_weight=None):
        positive = _reshape_like(F, positive, pred)
        negative = _reshape_like(F, negative, pred)
        loss = F.sum(F.square(positive - pred) - F.square(negative - pred),
                     axis=self._batch_axis, exclude=True)
        loss = F.relu(loss + self._margin)
        return _apply_weighting(F, loss, self._weight, sample_weight)


class CosineEmbeddingLoss(Loss):
    def __init__(self, weight=None, batch_axis=0, margin=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, input1, input2, label, sample_weight=None):
        input1 = _reshape_like(F, input1, input2)
        cos = F.sum(input1 * input2, axis=-1) / (
            F.norm(input1, axis=-1) * F.norm(input2, axis=-1) + 1e-12)
        label = F.reshape_like(label, cos)
        loss = F.where(label == 1, 1 - cos, F.relu(cos - self._margin))
        return _apply_weighting(F, loss, self._weight, sample_weight)


class ExpectedExitCELoss(Loss):
    """Cross-entropy over the ``exits`` exits of a looped model, weighted by
    the distribution its exit gates define, less ``beta`` times that
    distribution's entropy (arXiv:2510.25741, section 3)::

        p_t = gate_t * prod_{j<t}(1 - gate_j)  (t < exits),  p_last = the rest
        loss = mean over tokens of  sum_t p_t * CE(state_t . head^T, label)
                                    - beta * H(p)

    Takes what ``gluon.contrib.transformer.LoopedDecoderLM`` returns: states
    (exits, B, S, units), gates (exits, B, S), the head's weight (vocab,
    units), then the label (B, S). Each exit's logits are the head's product
    over that exit's states and go straight into the fused cross-entropy.
    The exits are NOT recomputed segments: the gradient of the loss in an
    exit's cross-entropy needs only the gates up to that exit, so an exit's
    backward pass can run right after its forward; as segments the exits
    ran every head product and log-sum-exp twice (PERF.md, PR 32). Gates,
    distribution, log-sum-exp and entropy are taken in float32."""

    def __init__(self, exits, beta=0.1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._exits = int(exits)
        self._beta = beta

    def hybrid_forward(self, F, states, gates, head_weight, label,
                       sample_weight=None):
        pick = lambda a, t: F.squeeze(  # noqa: E731
            F.slice_axis(a, axis=0, begin=t, end=t + 1), axis=0)
        lam = F.cast(gates, dtype="float32")
        probs, rest = [], None
        for t in range(self._exits - 1):
            g = pick(lam, t)
            probs.append(g if rest is None else g * rest)
            rest = (1.0 - g) if rest is None else rest * (1.0 - g)
        probs.append(F.ones_like(pick(lam, 0)) if rest is None else rest)
        loss = None
        for t, p in enumerate(probs):
            logits = F.dot(pick(states, t), head_weight, transpose_b=True)
            ce = F.softmax_cross_entropy(logits, label, per_row=True)
            # - beta * H(p) = beta * sum p log p; a gate that saturates
            # gives p = 0, whose term is 0 and not 0 * -inf
            term = p * ce + self._beta * p * F.log(F.maximum(p, 1e-30))
            loss = term if loss is None else loss + term
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)


class TiedHeadCELoss(Loss):
    """Mean softmax cross-entropy of a language model whose head is its
    embedding: takes the final normed states (B, S, units), the embedding's
    weight (vocab, units) and the label (B, S), as
    ``gluon.contrib.transformer.ZayaDecoderLM`` hands them over. The head's
    product runs here, straight into the fused cross-entropy
    (``F.softmax_cross_entropy(per_row=True)``), as ``ExpectedExitCELoss``
    does for one exit: the logits are the compute type's and no float32
    log-softmax of them is ever held."""

    def __init__(self, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def hybrid_forward(self, F, states, head_weight, label,
                       sample_weight=None):
        logits = F.dot(states, head_weight, transpose_b=True)
        loss = F.softmax_cross_entropy(logits, label, per_row=True)
        loss = _apply_weighting(F, loss, self._weight, sample_weight)
        return F.mean(loss, axis=self._batch_axis, exclude=True)
