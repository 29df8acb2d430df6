"""Gluon losses.

Reference: python/mxnet/gluon/loss.py (class Loss, L2Loss, L1Loss,
SigmoidBinaryCrossEntropyLoss, SoftmaxCrossEntropyLoss, KLDivLoss, CTCLoss,
HuberLoss, HingeLoss, SquaredHingeLoss, LogisticLoss, TripletLoss,
PoissonNLLLoss, CosineEmbeddingLoss).

Semantics preserved: `weight` scaling, per-example `sample_weight`
broadcasting via _apply_weighting, `batch_axis` mean reduction.
"""
from __future__ import annotations

import numpy as _np

from ..ndarray.ndarray import NDArray, invoke
from .. import ndarray as nd
from .. import telemetry
from .block import HybridBlock

__all__ = ["Loss", "L2Loss", "L1Loss", "SigmoidBinaryCrossEntropyLoss",
           "SigmoidBCELoss", "SoftmaxCrossEntropyLoss", "SoftmaxCELoss",
           "KLDivLoss", "CTCLoss", "HuberLoss", "HingeLoss",
           "SquaredHingeLoss", "LogisticLoss", "TripletLoss",
           "PoissonNLLLoss", "CosineEmbeddingLoss", "SDMLLoss"]


def count_cross_entropy(path):
    """The counter ``cross_entropy_calls{path}``: a softmax cross-entropy
    loss's calls by what computes them, ``fused`` (the
    ``sparse_softmax_cross_entropy`` operator) or ``composition``
    (`log_softmax` and a pick or a product).  Counted where the loss is
    called, so a compiled step counts each of its losses once, when it is
    traced, however often it runs."""
    telemetry.registry.counter(
        "cross_entropy_calls", "calls of the softmax cross-entropy loss by "
        "what computes them: the sparse_softmax_cross_entropy operator, or "
        "log_softmax and a pick or a product", {"path": path}).inc()


def _apply_weighting(loss, weight=None, sample_weight=None):
    """Reference: gluon.loss._apply_weighting."""
    if sample_weight is not None:
        loss = invoke("broadcast_mul", loss, sample_weight)
    if weight is not None:
        assert isinstance(weight, (int, float)), "weight must be a number"
        loss = loss * weight
    return loss


def _batch_mean(loss, batch_axis):
    """Mean over all axes except batch (reference: F.mean(loss, axis=
    self._batch_axis, exclude=True))."""
    axes = tuple(i for i in range(loss.ndim) if i != batch_axis)
    if not axes:
        return loss
    return loss.mean(axis=axes)


class Loss(HybridBlock):
    """Base loss (reference: gluon.loss.Loss)."""

    def __init__(self, weight, batch_axis, **kwargs):
        super().__init__(**kwargs)
        self._weight = weight
        self._batch_axis = batch_axis

    def __repr__(self):
        return "%s(batch_axis=%s, w=%s)" % (type(self).__name__,
                                            self._batch_axis, self._weight)


class L2Loss(Loss):
    """0.5 * (pred - label)^2 (reference scaling)."""

    def __init__(self, weight=1.0, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def forward(self, pred, label, sample_weight=None):
        label = label.reshape(pred.shape)
        loss = (pred - label) ** 2
        loss = _apply_weighting(loss, self._weight / 2, sample_weight)
        return _batch_mean(loss, self._batch_axis)


class L1Loss(Loss):
    def __init__(self, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def forward(self, pred, label, sample_weight=None):
        label = label.reshape(pred.shape)
        loss = (pred - label).abs()
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return _batch_mean(loss, self._batch_axis)


class SigmoidBinaryCrossEntropyLoss(Loss):
    """Reference: SigmoidBCELoss — numerically-stable log-sum-exp form when
    from_sigmoid=False, optional pos_weight."""

    def __init__(self, from_sigmoid=False, weight=None, batch_axis=0,
                 **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_sigmoid = from_sigmoid

    def forward(self, pred, label, sample_weight=None, pos_weight=None):
        label = label.reshape(pred.shape)
        if not self._from_sigmoid:
            if pos_weight is None:
                # max(x,0) - x*z + log(1+exp(-|x|))
                loss = pred.relu() - pred * label + \
                    (1.0 + (-pred.abs()).exp()).log()
            else:
                log_weight = 1.0 + invoke("broadcast_mul", label,
                                          pos_weight - 1.0)
                loss = pred - pred * label + log_weight * \
                    ((1.0 + (-pred.abs()).exp()).log() + (-pred).relu())
        else:
            eps = 1e-12
            if pos_weight is None:
                loss = -((pred + eps).log() * label +
                         (1.0 - pred + eps).log() * (1.0 - label))
            else:
                loss = -(invoke("broadcast_mul", (pred + eps).log() * label,
                                pos_weight) +
                         (1.0 - pred + eps).log() * (1.0 - label))
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return _batch_mean(loss, self._batch_axis)


SigmoidBCELoss = SigmoidBinaryCrossEntropyLoss


class SoftmaxCrossEntropyLoss(Loss):
    """Reference: SoftmaxCELoss.  Sparse labels on logits take the
    ``sparse_softmax_cross_entropy`` operator (logsumexp less the picked
    logit, in float32: no float32 ``[..., classes]`` tensor is written);
    `from_logits` and dense labels keep `log_softmax` and a pick or a
    product."""

    def __init__(self, axis=-1, sparse_label=True, from_logits=False,
                 weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._axis = axis
        self._sparse_label = sparse_label
        self._from_logits = from_logits

    def forward(self, pred, label, sample_weight=None):
        if self._sparse_label and not self._from_logits:
            count_cross_entropy("fused")
            loss = invoke("sparse_softmax_cross_entropy", pred, label,
                          axis=self._axis)
        else:
            count_cross_entropy("composition")
            if not self._from_logits:
                pred = pred.log_softmax(axis=self._axis)
            if self._sparse_label:
                loss = -invoke("pick", pred, label, axis=self._axis,
                               keepdims=False)
            else:
                label = label.reshape(pred.shape)
                loss = -(pred * label).sum(axis=self._axis, keepdims=False)
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return _batch_mean(loss, self._batch_axis)


SoftmaxCELoss = SoftmaxCrossEntropyLoss


class KLDivLoss(Loss):
    def __init__(self, from_logits=True, axis=-1, weight=None, batch_axis=0,
                 **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_logits = from_logits
        self._axis = axis

    def forward(self, pred, label, sample_weight=None):
        if not self._from_logits:
            pred = pred.log_softmax(axis=self._axis)
        loss = label * ((label + 1e-12).log() - pred)
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return _batch_mean(loss, self._batch_axis)


class CTCLoss(Loss):
    """Connectionist temporal classification (reference: gluon.loss.CTCLoss
    over src/operator/nn/ctc_loss.cc).  TPU-native: the alpha recursion runs
    as a lax.scan inside the `CTCLoss` op (ops/nn.py) — static shapes, no
    cuDNN CTC needed."""

    def __init__(self, layout="NTC", label_layout="NT", weight=None, **kwargs):
        batch_axis = label_layout.find("N")
        super().__init__(weight, batch_axis, **kwargs)
        self._layout = layout
        self._label_layout = label_layout

    def forward(self, pred, label, pred_lengths=None, label_lengths=None,
                sample_weight=None):
        if self._layout == "NTC":
            pred = pred.transpose((1, 0, 2))  # -> TNC
        if self._label_layout == "TN":
            label = label.transpose((1, 0))
        loss = invoke("CTCLoss", pred, label,
                      None if pred_lengths is None else pred_lengths,
                      None if label_lengths is None else label_lengths)
        return _apply_weighting(loss, self._weight, sample_weight)


class HuberLoss(Loss):
    def __init__(self, rho=1.0, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._rho = rho

    def forward(self, pred, label, sample_weight=None):
        label = label.reshape(pred.shape)
        err = (pred - label).abs()
        loss = nd.where((err > self._rho),
                        err - 0.5 * self._rho,
                        (0.5 / self._rho) * (err ** 2))
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return _batch_mean(loss, self._batch_axis)


class HingeLoss(Loss):
    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def forward(self, pred, label, sample_weight=None):
        label = label.reshape(pred.shape)
        loss = (self._margin - pred * label).relu()
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return _batch_mean(loss, self._batch_axis)


class SquaredHingeLoss(Loss):
    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def forward(self, pred, label, sample_weight=None):
        label = label.reshape(pred.shape)
        loss = (self._margin - pred * label).relu() ** 2
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return _batch_mean(loss, self._batch_axis)


class LogisticLoss(Loss):
    def __init__(self, weight=None, batch_axis=0, label_format="signed",
                 **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._label_format = label_format
        if label_format not in ("signed", "binary"):
            raise ValueError("label_format must be signed or binary")

    def forward(self, pred, label, sample_weight=None):
        label = label.reshape(pred.shape)
        if self._label_format == "signed":
            label = (label + 1.0) / 2.0
        loss = pred.relu() - pred * label + (1.0 + (-pred.abs()).exp()).log()
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return _batch_mean(loss, self._batch_axis)


class TripletLoss(Loss):
    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def forward(self, pred, positive, negative, sample_weight=None):
        positive = positive.reshape(pred.shape)
        negative = negative.reshape(pred.shape)
        axes = tuple(range(1, pred.ndim))
        loss = ((pred - positive) ** 2 - (pred - negative) ** 2).sum(axis=axes)
        loss = (loss + self._margin).relu()
        return _apply_weighting(loss, self._weight, sample_weight)


class PoissonNLLLoss(Loss):
    def __init__(self, weight=None, from_logits=True, batch_axis=0,
                 compute_full=False, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_logits = from_logits
        self._compute_full = compute_full

    def forward(self, pred, target, sample_weight=None, epsilon=1e-08):
        target = target.reshape(pred.shape)
        if self._from_logits:
            loss = pred.exp() - target * pred
        else:
            loss = pred - target * (pred + epsilon).log()
        if self._compute_full:
            # Stirling approximation of log(target!)
            stirling = target * target.log() - target + \
                0.5 * (2 * _np.pi * target).log()
            stirling = nd.where(target <= 1, stirling * 0, stirling)
            loss = loss + stirling
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return loss.mean()


class CosineEmbeddingLoss(Loss):
    def __init__(self, weight=None, batch_axis=0, margin=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def forward(self, input1, input2, label, sample_weight=None):
        input2 = input2.reshape(input1.shape)
        dot = (input1 * input2).sum(axis=-1)
        n1 = (input1 ** 2).sum(axis=-1).sqrt()
        n2 = (input2 ** 2).sum(axis=-1).sqrt()
        cos = dot / (n1 * n2 + 1e-12)
        label = label.reshape(cos.shape)
        loss = nd.where(label == 1, 1.0 - cos, (cos - self._margin).relu())
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return loss


class SDMLLoss(Loss):
    """Smoothed deep metric learning loss (reference: gluon.loss.SDMLLoss):
    treats a (x1[i], x2[i]) batch as N retrieval problems — the pairwise
    distance matrix is turned into a distribution with softmax(-d) and
    pulled toward a label-smoothed identity via KL divergence."""

    def __init__(self, smoothing_parameter=0.3, weight=1.0, batch_axis=0,
                 **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._smoothing = smoothing_parameter
        self._kl = KLDivLoss(from_logits=True)

    def forward(self, x1, x2):
        from .. import nd as _nd
        n = x1.shape[0]
        # squared euclidean distances between every (x1[i], x2[j]) pair
        x1sq = (x1 * x1).sum(axis=1).reshape((n, 1))
        x2sq = (x2 * x2).sum(axis=1).reshape((1, n))
        dist = x1sq + x2sq - 2.0 * _nd.dot(x1, x2.T)
        log_prob = _nd.log_softmax(-dist, axis=1)
        # label-smoothed identity target: diagonal keeps 1-s, the rest
        # shares s/(N-1)
        eye = _nd.eye(n)
        labels = eye * (1.0 - self._smoothing) + \
            (1.0 - eye) * (self._smoothing / max(n - 1, 1))
        return self._kl(log_prob, labels)
