"""Loss criteria (reference ``nn/abstractnn/AbstractCriterion.scala:49`` and
the 24 criterion files under ``$B/nn/``).

Same design as modules: stateful objects with ``forward(input, target)``
returning a scalar loss, but every criterion's math is pure jax.numpy, so the
training loop composes ``criterion.apply`` inside one jitted step and gets the
gradient from ``jax.grad`` (replacing each reference criterion's hand-written
``updateGradInput``).

Label convention follows Torch/BigDL: class targets are **1-based** indices.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import Activity
from bigdl_tpu.utils.table import Table


class Criterion:
    """Base criterion (reference ``AbstractCriterion``)."""

    def __init__(self):
        self.output = None
        self.grad_input = None

    def update_output(self, input: Activity, target: Activity):
        raise NotImplementedError

    def forward(self, input: Activity, target: Activity):
        self.output = self.update_output(input, target)
        return self.output

    def __call__(self, input: Activity, target: Activity):
        return self.forward(input, target)

    def apply(self, input: Activity, target: Activity):
        """Pure loss (no state mutation) — what the jitted step traces."""
        return self.update_output(input, target)

    def backward(self, input: Activity, target: Activity):
        self.grad_input = jax.grad(lambda x: self.update_output(x, target))(input)
        return self.grad_input


def _reduce(x: jax.Array, size_average: bool, n: Optional[int] = None):
    total = jnp.sum(x)
    if size_average:
        return total / (x.size if n is None else n)
    return total


def _one_hot_1based(target: jax.Array, n_classes: int) -> jax.Array:
    return jax.nn.one_hot(target.astype(jnp.int32) - 1, n_classes)


class ClassNLLCriterion(Criterion):
    """Negative log-likelihood over log-probabilities
    (reference ``nn/ClassNLLCriterion.scala:56``).

    ``input``: (N, C) log-probabilities (e.g. LogSoftMax output) or (C,).
    ``target``: (N,) 1-based class indices. Optional per-class ``weights``.
    """

    def __init__(self, weights=None, size_average: bool = True):
        super().__init__()
        self.weights = None if weights is None else jnp.asarray(weights)
        self.size_average = size_average

    def update_output(self, input, target):
        if input.ndim == 1:
            input = input[None, :]
            target = jnp.reshape(target, (1,))
        idx = target.astype(jnp.int32) - 1
        picked = jnp.take_along_axis(input, idx[:, None], axis=1)[:, 0]
        if self.weights is not None:
            w = self.weights[idx]
            loss = -jnp.sum(w * picked)
            return loss / jnp.sum(w) if self.size_average else loss
        return -_reduce(picked, self.size_average)


class CrossEntropyCriterion(Criterion):
    """LogSoftMax + ClassNLL fused (reference ``CrossEntropyCriterion``).
    TPU note: the fused form is one XLA logsumexp, numerically stable."""

    def __init__(self, weights=None, size_average: bool = True):
        super().__init__()
        self.weights = None if weights is None else jnp.asarray(weights)
        self.size_average = size_average

    def update_output(self, input, target):
        logp = jax.nn.log_softmax(input, axis=-1)
        return ClassNLLCriterion(self.weights, self.size_average).update_output(logp, target)


class MSECriterion(Criterion):
    """Mean squared error (reference ``nn/MSECriterion.scala``)."""

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def update_output(self, input, target):
        return _reduce((input - target) ** 2, self.size_average)


class AbsCriterion(Criterion):
    """Mean absolute error (reference ``nn/AbsCriterion.scala``)."""

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def update_output(self, input, target):
        return _reduce(jnp.abs(input - target), self.size_average)


class BCECriterion(Criterion):
    """Binary cross-entropy on probabilities (reference ``nn/BCECriterion.scala``)."""

    EPS = 1e-12

    def __init__(self, weights=None, size_average: bool = True):
        super().__init__()
        self.weights = None if weights is None else jnp.asarray(weights)
        self.size_average = size_average

    def update_output(self, input, target):
        x = jnp.clip(input, self.EPS, 1.0 - self.EPS)
        ll = target * jnp.log(x) + (1.0 - target) * jnp.log(1.0 - x)
        if self.weights is not None:
            ll = ll * self.weights
        return -_reduce(ll, self.size_average)


class SmoothL1Criterion(Criterion):
    """Huber-style smooth L1 (reference ``nn/SmoothL1Criterion.scala``)."""

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def update_output(self, input, target):
        d = jnp.abs(input - target)
        loss = jnp.where(d < 1.0, 0.5 * d * d, d - 0.5)
        return _reduce(loss, self.size_average)


class SmoothL1CriterionWithWeights(Criterion):
    """Smooth L1 with inside/outside weights and sigma
    (reference ``nn/SmoothL1CriterionWithWeights.scala``, Fast-RCNN style)."""

    def __init__(self, sigma: float = 1.0, num: int = 0):
        super().__init__()
        self.sigma2 = sigma * sigma
        self.num = num

    def update_output(self, input, target):
        if isinstance(target, Table):
            t, inw, outw = target[1], target[2], target[3]
        else:
            t, inw, outw = target, None, None
        d = input - t
        if inw is not None:
            d = d * inw
        ad = jnp.abs(d)
        loss = jnp.where(ad < 1.0 / self.sigma2,
                         0.5 * self.sigma2 * d * d,
                         ad - 0.5 / self.sigma2)
        if outw is not None:
            loss = loss * outw
        total = jnp.sum(loss)
        return total / self.num if self.num > 0 else total


class MarginCriterion(Criterion):
    """Hinge loss for two-class {1,-1} targets (reference ``nn/MarginCriterion.scala``)."""

    def __init__(self, margin: float = 1.0, size_average: bool = True):
        super().__init__()
        self.margin = margin
        self.size_average = size_average

    def update_output(self, input, target):
        return _reduce(jnp.maximum(0.0, self.margin - input * target),
                       self.size_average)


class MarginRankingCriterion(Criterion):
    """Ranking hinge on pairs (reference ``nn/MarginRankingCriterion.scala``).
    ``input`` is a Table {1: x1, 2: x2}; target y ∈ {1,-1}."""

    def __init__(self, margin: float = 1.0, size_average: bool = True):
        super().__init__()
        self.margin = margin
        self.size_average = size_average

    def update_output(self, input, target):
        x1, x2 = input[1], input[2]
        y = target[1] if isinstance(target, Table) else target
        loss = jnp.maximum(0.0, -y * (x1 - x2) + self.margin)
        return _reduce(loss, self.size_average)


class HingeEmbeddingCriterion(Criterion):
    """reference ``nn/HingeEmbeddingCriterion.scala``: y=1 → x, y=-1 → max(0, m-x)."""

    def __init__(self, margin: float = 1.0, size_average: bool = True):
        super().__init__()
        self.margin = margin
        self.size_average = size_average

    def update_output(self, input, target):
        loss = jnp.where(target == 1, input,
                         jnp.maximum(0.0, self.margin - input))
        return _reduce(loss, self.size_average)


class L1HingeEmbeddingCriterion(Criterion):
    """Pairwise L1-distance hinge (reference ``nn/L1HingeEmbeddingCriterion.scala``)."""

    def __init__(self, margin: float = 1.0):
        super().__init__()
        self.margin = margin

    def update_output(self, input, target):
        d = jnp.sum(jnp.abs(input[1] - input[2]))
        y = target[1] if isinstance(target, Table) else jnp.reshape(target, ())
        return jnp.where(y == 1, d, jnp.maximum(0.0, self.margin - d))


class CosineEmbeddingCriterion(Criterion):
    """reference ``nn/CosineEmbeddingCriterion.scala:196``."""

    def __init__(self, margin: float = 0.0, size_average: bool = True):
        super().__init__()
        self.margin = margin
        self.size_average = size_average

    def update_output(self, input, target):
        x1, x2 = input[1], input[2]
        if x1.ndim == 1:
            x1, x2 = x1[None, :], x2[None, :]
        y = target[1] if isinstance(target, Table) else target
        y = jnp.reshape(y, (-1,))
        cos = jnp.sum(x1 * x2, axis=-1) / jnp.maximum(
            jnp.linalg.norm(x1, axis=-1) * jnp.linalg.norm(x2, axis=-1), 1e-12)
        loss = jnp.where(y == 1, 1.0 - cos, jnp.maximum(0.0, cos - self.margin))
        return _reduce(loss, self.size_average)


class DistKLDivCriterion(Criterion):
    """KL(target ‖ input) with log-prob input (reference ``nn/DistKLDivCriterion.scala``)."""

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def update_output(self, input, target):
        contrib = jnp.where(target > 0, target * (jnp.log(jnp.maximum(target, 1e-12)) - input), 0.0)
        return _reduce(contrib, self.size_average)


class SoftMarginCriterion(Criterion):
    """log(1+exp(-y·x)) (reference ``nn/SoftMarginCriterion.scala``)."""

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def update_output(self, input, target):
        return _reduce(jnp.log1p(jnp.exp(-input * target)), self.size_average)


class MultiLabelSoftMarginCriterion(Criterion):
    """Multi-label one-vs-all BCE on logits
    (reference ``nn/MultiLabelSoftMarginCriterion.scala``)."""

    def __init__(self, weights=None, size_average: bool = True):
        super().__init__()
        self.weights = None if weights is None else jnp.asarray(weights)
        self.size_average = size_average

    def update_output(self, input, target):
        # Stable sigmoid cross-entropy.
        ll = target * jax.nn.log_sigmoid(input) + (1 - target) * jax.nn.log_sigmoid(-input)
        if self.weights is not None:
            ll = ll * self.weights
        n = input.shape[0] if input.ndim > 1 else 1
        total = -jnp.sum(ll) / input.shape[-1]
        return total / n if self.size_average else total


class MultiMarginCriterion(Criterion):
    """Multi-class margin loss (reference ``nn/MultiMarginCriterion.scala:187``)."""

    def __init__(self, p: int = 1, weights=None, margin: float = 1.0,
                 size_average: bool = True):
        super().__init__()
        assert p in (1, 2)
        self.p = p
        self.weights = None if weights is None else jnp.asarray(weights)
        self.margin = margin
        self.size_average = size_average

    def update_output(self, input, target):
        if input.ndim == 1:
            input = input[None, :]
            target = jnp.reshape(target, (1,))
        n, c = input.shape
        idx = target.astype(jnp.int32) - 1
        x_y = jnp.take_along_axis(input, idx[:, None], axis=1)
        m = jnp.maximum(0.0, self.margin - x_y + input)
        if self.p == 2:
            m = m * m
        if self.weights is not None:
            m = m * self.weights[idx][:, None]
        # exclude the target column itself
        mask = 1.0 - jax.nn.one_hot(idx, c)
        loss = jnp.sum(m * mask, axis=1) / c
        return _reduce(loss, self.size_average, n) if self.size_average else jnp.sum(loss)


class MultiLabelMarginCriterion(Criterion):
    """Multi-label margin (reference ``nn/MultiLabelMarginCriterion.scala:212``).

    ``target`` holds 1-based label indices padded with zeros; for each valid
    label j and each non-label k: max(0, 1 - (x[j] - x[k])) / C.
    """

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def update_output(self, input, target):
        if input.ndim == 1:
            input = input[None, :]
            target = jnp.reshape(target, (1, -1))
        n, c = input.shape

        def per_sample(x, t):
            t = t.astype(jnp.int32)
            valid = t > 0
            idx = jnp.maximum(t - 1, 0)
            is_label = jnp.zeros((c,), bool).at[idx].set(valid, mode="drop")
            x_t = jnp.where(valid, x[idx], 0.0)                       # (L,)
            margins = jnp.maximum(0.0, 1.0 - (x_t[:, None] - x[None, :]))  # (L, C)
            margins = margins * valid[:, None] * (~is_label)[None, :]
            return jnp.sum(margins) / c

        loss = jax.vmap(per_sample)(input, target)
        return _reduce(loss, self.size_average, n) if self.size_average else jnp.sum(loss)


class ClassSimplexCriterion(MSECriterion):
    """MSE against simplex-embedded class targets
    (reference ``nn/ClassSimplexCriterion.scala``)."""

    def __init__(self, n_classes: int):
        super().__init__(size_average=True)
        self.n_classes = n_classes
        self.simplex = jnp.asarray(self._build_simplex(n_classes))

    @staticmethod
    def _build_simplex(n: int):
        import numpy as np
        a = np.zeros((n, n), dtype=np.float32)
        a[0, 0] = 1.0
        for k in range(1, n - 1):
            s = float(np.dot(a[k, :k], a[k - 1, :k]))
            a[k, k - 1] = (1.0 - s) / a[k - 1, k - 1] if a[k - 1, k - 1] != 0 else 0.0
            norm2 = float(np.dot(a[k, :k + 1], a[k, :k + 1]))
            a[k, k] = np.sqrt(max(0.0, 1.0 - norm2))
        if n > 1:
            a[n - 1] = a[n - 2]
            a[n - 1, n - 1] *= -1
        return a

    def update_output(self, input, target):
        t = self.simplex[target.astype(jnp.int32) - 1]
        return super().update_output(input, t)


class DiceCoefficientCriterion(Criterion):
    """1 - Dice overlap (reference ``nn/DiceCoefficientCriterion.scala:147``)."""

    def __init__(self, size_average: bool = True, epsilon: float = 1.0):
        super().__init__()
        self.size_average = size_average
        self.epsilon = epsilon

    def update_output(self, input, target):
        if input.ndim == 1:
            input = input[None, :]
            target = jnp.reshape(target, (1, -1))
        inter = jnp.sum(input * target, axis=1)
        union = jnp.sum(input, axis=1) + jnp.sum(target, axis=1)
        dice = (2.0 * inter + self.epsilon) / (union + self.epsilon)
        loss = 1.0 - dice
        n = input.shape[0]
        return jnp.sum(loss) / n if self.size_average else jnp.sum(loss)


class L1Cost(Criterion):
    """Sum of absolute values of the input (reference ``nn/L1Cost.scala``)."""

    def update_output(self, input, target=None):
        return jnp.sum(jnp.abs(input))


class SoftmaxWithCriterion(Criterion):
    """Caffe-style softmax loss with ignore label / normalization modes
    (reference ``nn/SoftmaxWithCriterion.scala:160``). Input (N, C, ...)."""

    def __init__(self, ignore_label: Optional[int] = None,
                 normalize_mode: str = "VALID"):
        super().__init__()
        self.ignore_label = ignore_label
        self.normalize_mode = normalize_mode

    def update_output(self, input, target):
        # input (N, C, *spatial), target (N, *spatial) 1-based.
        logp = jax.nn.log_softmax(input, axis=1)
        idx = target.astype(jnp.int32) - 1
        picked = jnp.take_along_axis(logp, idx[:, None, ...], axis=1)[:, 0, ...]
        if self.ignore_label is not None:
            valid = target != self.ignore_label
            picked = jnp.where(valid, picked, 0.0)
            count = jnp.sum(valid)
        else:
            count = picked.size
        total = -jnp.sum(picked)
        mode = self.normalize_mode.upper()
        if mode == "VALID":
            return total / jnp.maximum(count, 1)
        if mode == "FULL":
            return total / picked.size
        if mode == "BATCH_SIZE":
            return total / input.shape[0]
        return total  # NONE


class ParallelCriterion(Criterion):
    """Weighted sum of criteria over Table inputs/targets
    (reference ``nn/ParallelCriterion.scala``)."""

    def __init__(self, repeat_target: bool = False):
        super().__init__()
        self.criterions = []
        self.weights = []
        self.repeat_target = repeat_target

    def add(self, criterion: Criterion, weight: float = 1.0) -> "ParallelCriterion":
        self.criterions.append(criterion)
        self.weights.append(weight)
        return self

    def update_output(self, input, target):
        total = 0.0
        for i, (c, w) in enumerate(zip(self.criterions, self.weights), start=1):
            t = target if self.repeat_target else target[i]
            total = total + w * c.update_output(input[i], t)
        return total


class MultiCriterion(Criterion):
    """Weighted sum of criteria over the *same* input
    (reference ``nn/MultiCriterion.scala``)."""

    def __init__(self):
        super().__init__()
        self.criterions = []
        self.weights = []

    def add(self, criterion: Criterion, weight: float = 1.0) -> "MultiCriterion":
        self.criterions.append(criterion)
        self.weights.append(weight)
        return self

    def update_output(self, input, target):
        total = 0.0
        for c, w in zip(self.criterions, self.weights):
            total = total + w * c.update_output(input, target)
        return total


class CriterionTable(Criterion):
    """Wrap a criterion to take {input, target} as a Table
    (reference ``nn/CriterionTable.scala``)."""

    def __init__(self, criterion: Criterion):
        super().__init__()
        self.criterion = criterion

    def update_output(self, input, target=None):
        return self.criterion.update_output(input[1], input[2])


class TimeDistributedCriterion(Criterion):
    """Apply a criterion across the time dimension
    (reference ``nn/TimeDistributedCriterion.scala:146``).

    Input (N, T, ...), target (N, T, ...): merges batch and time, applies the
    inner criterion once — on TPU this is a reshape, not a per-step loop.
    """

    def __init__(self, criterion: Criterion, size_average: bool = False):
        super().__init__()
        self.criterion = criterion
        self.size_average = size_average

    def update_output(self, input, target):
        n, t = input.shape[0], input.shape[1]
        x = jnp.reshape(input, (n * t,) + input.shape[2:])
        y = jnp.reshape(target, (n * t,) + target.shape[2:])
        loss = self.criterion.update_output(x, y)
        return loss / t if self.size_average else loss


def exit_distribution(logits):
    """(p, sum_t p log p) of a looped decoder's exit gate ``logits`` (P,
    ...): the distribution over the P passes a position, ``p_t = lam_t
    prod_{j<t}(1 - lam_j)`` with ``lam = sigmoid(logits)``, the last pass
    taking what the others left, and its negative entropy; float32, from
    log-sigmoids (``FusedLMHeadCriterion``)."""
    g = logits.astype(jnp.float32)
    stay = jax.nn.log_sigmoid(-g[:-1])              # log(1 - lam_j)
    left = jnp.concatenate([jnp.zeros_like(g[:1]),
                            jnp.cumsum(stay, axis=0)])
    log_p = left + jnp.concatenate(
        [jax.nn.log_sigmoid(g[:-1]), jnp.zeros_like(g[:1])])
    p = jnp.exp(log_p)
    return p, jnp.sum(p * log_p, axis=0)


class FusedLMHeadCriterion(Criterion):
    """Row-tiled cross-entropy paired with ``nn.LMHead``.

    Training path: ``input`` is the Table ``(hidden, weight[, bias])`` that
    ``LMHead`` emits in training mode; the loss is computed by
    ``ops/lm_head_ce.fused_lm_head_ce`` — one scan over tiles of ``chunk``
    rows (default: from the shapes) that forms the loss and its gradients
    while a tile's logits are live, so neither the logits nor their
    cotangent ever materialise at (N, V).

    Validation path: when ``input`` is a plain array it is taken as
    LOG-PROBABILITIES over the trailing axis (LMHead's eval output) and
    scored as mean NLL over all leading positions — so the same criterion
    instance works inside ``optim.Loss`` during validation.

    Numerically equal (to fp32 tolerance) to
    ``TimeDistributedCriterion(ClassNLLCriterion())`` on the unfused tail
    (the inner NLL's size-average already spans the merged batch*time axis,
    i.e. the loss is the flat mean over every position).

    Multi-token prediction: where the training Table also carries ``mtp``
    (the stream of a ``nn.MTPModule``, whose position i predicts the token
    after next) and ``mtp_weight``, the loss is ``L_main + mtp_weight *
    L_mtp``: the second term is the same fused pass over the same head
    against the targets shifted one to the left, the last position left
    out (``ignore_index``, or 0 where none is set: no 1-based id), under
    the scope ``mtp``.

    A looped decoder's exit gate
    (``models.hybrid.build_hybrid_lm(passes=P, exit_gate=True)``): where
    the Table carries ``exit_logits`` ((P, B, T) float32, the gate's logit
    of every pass and position) and ``exit_beta``, ``hidden`` is the P
    passes' streams stacked (P, B, T, E) and the loss is the expected
    cross-entropy under the exit distribution less ``exit_beta`` times its
    entropy, a mean over the tokens::

        lam_t = sigmoid(g_t);  p_t = lam_t prod_{j<t}(1 - lam_j)  (t < P),
        p_P = prod_{j<P}(1 - lam_j)
        L = mean_i [ sum_t p_{i,t} CE_{i,t} + exit_beta sum_t p log p ]

    ``lam``, ``p`` and the entropy are float32, from log-sigmoids, under
    the scope ``loop_exit``; the P x B x T cross-entropies are ONE fused
    pass with ``p`` as its weight a row (``fused_lm_head_ce(row_weight=)``),
    whose gradient with respect to ``p`` is what trains the gate.
    """

    def __init__(self, chunk: Optional[int] = None,
                 size_average: bool = True,
                 ignore_index: Optional[int] = None):
        super().__init__()
        self.chunk = chunk
        self.size_average = size_average
        self.ignore_index = ignore_index

    def update_output(self, input, target):
        from bigdl_tpu.ops.lm_head_ce import fused_lm_head_ce
        if isinstance(input, (Table, tuple, list)):
            nxt = None
            if isinstance(input, Table):
                hidden, weight, bias = input[1], input[2], input.get(3)
                nxt = input.get("mtp")
                if "exit_logits" in input:
                    return self._exit_loss(hidden, weight, bias, target,
                                           input["exit_logits"],
                                           input["exit_beta"])
            else:
                hidden, weight = input[0], input[1]
                bias = input[2] if len(input) >= 3 else None
            loss = fused_lm_head_ce(hidden, weight, bias, target,
                                    chunk=self.chunk,
                                    size_average=self.size_average,
                                    ignore_index=self.ignore_index)
            if nxt is None:
                return loss
            left_out = 0 if self.ignore_index is None else self.ignore_index
            with jax.named_scope("mtp"):
                after_next = jnp.concatenate(
                    [target[:, 1:], jnp.full_like(target[:, :1], left_out)],
                    axis=1)
                return loss + input["mtp_weight"] * fused_lm_head_ce(
                    nxt, weight, bias, after_next, chunk=self.chunk,
                    size_average=self.size_average, ignore_index=left_out)
        # eval fallback: input already log-probs (B, S, V) or (N, V)
        logp = input
        tgt = target.astype(jnp.int32) - 1
        picked = jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        if self.ignore_index is not None:
            valid = target.astype(jnp.int32) != int(self.ignore_index)
            total = -jnp.sum(jnp.where(valid, picked, 0.0))
            if self.size_average:
                return total / jnp.maximum(jnp.sum(valid.astype(
                    jnp.float32)), 1.0)
            return total
        return -_reduce(picked, self.size_average)

    def _exit_loss(self, hidden, weight, bias, target, logits, beta):
        """The looped decoder's loss (class docstring) from the P streams,
        the gate's logits and the entropy's weight."""
        from bigdl_tpu.ops.lm_head_ce import fused_lm_head_ce
        valid = None if self.ignore_index is None \
            else target.astype(jnp.int32) != int(self.ignore_index)
        with jax.named_scope("loop_exit"):
            p, neg_entropy = exit_distribution(logits)
            if valid is not None:
                neg_entropy = jnp.where(valid, neg_entropy, 0.0)
        ce = fused_lm_head_ce(
            hidden, weight, bias, jnp.broadcast_to(target, logits.shape),
            chunk=self.chunk, size_average=False,
            ignore_index=self.ignore_index, row_weight=p)
        with jax.named_scope("loop_exit"):
            loss = ce + beta * jnp.sum(neg_entropy)
            if not self.size_average:
                return loss
            return loss / (target.size if valid is None else jnp.maximum(
                jnp.sum(valid.astype(jnp.float32)), 1.0))
