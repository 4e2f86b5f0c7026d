"""Normalization layers (reference ``nn/BatchNormalization.scala:50``,
``SpatialBatchNormalization``, ``SpatialCrossMapLRN.scala:235``,
``Normalize.scala:187``, and the Divisive/Subtractive/Contrastive trio).

The reference threads per-channel tasks over ``Engine.model``
(``BatchNormalization.scala:171,240,471,559``); here the whole reduction is
one fused XLA op. Running statistics are module *buffers*: inside a jitted
training step they are threaded functionally (``functional_apply`` returns the
new buffer tree) — the TPU-safe version of the reference's in-place updates.

Layout: channels-last; the feature/channel dim is the last dim everywhere.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn import initialization as init
from bigdl_tpu.nn.module import TensorModule


class BatchNormalization(TensorModule):
    """Batch norm over (N, C) inputs (reference ``nn/BatchNormalization.scala:50``)."""

    def __init__(self, n_output: int, eps: float = 1e-5, momentum: float = 0.1,
                 affine: bool = True):
        super().__init__()
        self.n_output = n_output
        self.eps = eps
        self.momentum = momentum
        self.affine = affine
        if affine:
            self.register_parameter("weight", init.ones((n_output,)))
            self.register_parameter("bias", init.zeros((n_output,)))
        self.register_buffer("running_mean", init.zeros((n_output,)))
        self.register_buffer("running_var", init.ones((n_output,)))

    def update_output(self, input):
        if self.training:
            from bigdl_tpu.ops.batch_norm import batch_norm_train
            if self.affine:
                gamma, beta = self.weight, self.bias
            else:
                gamma = jnp.ones((self.n_output,), input.dtype)
                beta = jnp.zeros((self.n_output,), input.dtype)
            out, mean, var = batch_norm_train(input, gamma, beta, self.eps)
            # running stats: unbiased variance, momentum blend; they feed
            # buffers only, never the loss. functional_apply collects the
            # assignment.
            n = input.size // input.shape[-1]
            mean = jax.lax.stop_gradient(mean)
            unbiased = jax.lax.stop_gradient(var * (n / max(1, n - 1)))
            self.running_mean = ((1 - self.momentum) * self.running_mean
                                 + self.momentum * mean)
            self.running_var = ((1 - self.momentum) * self.running_var
                                + self.momentum * unbiased)
            return out
        mean, var = self.running_mean, self.running_var
        inv = jax.lax.rsqrt(var + self.eps)
        out = (input - mean) * inv
        if self.affine:
            out = out * self.weight + self.bias
        return out

    def __repr__(self):
        return f"{type(self).__name__}({self.n_output})"


class SpatialBatchNormalization(BatchNormalization):
    """Batch norm over (N, H, W, C) — same math, channel = last dim
    (reference ``nn/SpatialBatchNormalization.scala``)."""


class VolumetricBatchNormalization(BatchNormalization):
    """Batch norm over (N, D, H, W, C)."""


class InputNormalize(TensorModule):
    """Device-side input normalization: cast the incoming batch (uint8
    from the host decode path, or any dtype) to ``dtype`` and apply
    per-channel ``(x - mean) / std``.

    The TPU-first half of the ingest pipeline (round 5): the host ships
    RAW uint8 batches — 4x fewer host->device bytes than f32, which is
    the binding ingest constraint when the host link is — and XLA
    fuses the cast+normalize into the first convolution's input read.
    Pairs with ``dataset.image.NativeBGRBatchDecoder(device_normalize=
    True)``. No parameters; gradients pass through the affine map.
    """

    def __init__(self, mean, std, dtype=jnp.float32):
        super().__init__()
        self.mean = np.asarray(mean, np.float32)
        self.rstd = 1.0 / np.asarray(std, np.float32)
        self.dtype = dtype

    def update_output(self, input):
        x = input.astype(self.dtype)
        return (x - jnp.asarray(self.mean, self.dtype)) \
            * jnp.asarray(self.rstd, self.dtype)

    def __repr__(self):
        return f"InputNormalize(mean={self.mean}, std={1.0 / self.rstd})"


class SpatialCrossMapLRN(TensorModule):
    """AlexNet-style local response normalization across channels
    (reference ``nn/SpatialCrossMapLRN.scala:235``).

    TPU-native: the sliding-window channel sum is a 1-wide reduce_window over
    the channel dim, not the reference's per-frame threaded loop.
    """

    def __init__(self, size: int = 5, alpha: float = 1.0, beta: float = 0.75,
                 k: float = 1.0):
        super().__init__()
        self.size = size
        self.alpha, self.beta, self.k = alpha, beta, k

    def update_output(self, input):
        sq = input * input
        pre = self.size // 2
        post = self.size - pre - 1
        window_sum = jax.lax.reduce_window(
            sq, 0.0, jax.lax.add,
            window_dimensions=(1,) * (input.ndim - 1) + (self.size,),
            window_strides=(1,) * input.ndim,
            padding=((0, 0),) * (input.ndim - 1) + ((pre, post),))
        scale = jnp.power(self.k + window_sum * (self.alpha / self.size), -self.beta)
        return input * scale


class Normalize(TensorModule):
    """Lp-normalise each sample to unit norm (reference ``nn/Normalize.scala:187``)."""

    def __init__(self, p: float = 2.0, eps: float = 1e-10):
        super().__init__()
        self.p, self.eps = p, eps

    def update_output(self, input):
        if np.isinf(self.p):
            norm = jnp.max(jnp.abs(input), axis=-1, keepdims=True)
        else:
            norm = jnp.power(jnp.sum(jnp.power(jnp.abs(input), self.p),
                                     axis=-1, keepdims=True), 1.0 / self.p)
        return input / (norm + self.eps)


def _gaussian2d(kernel_size: int) -> np.ndarray:
    """Normalised 2-D gaussian used as the default local-normalization kernel."""
    sigma = 0.25 * kernel_size
    ax = np.arange(kernel_size) - (kernel_size - 1) / 2.0
    g = np.exp(-(ax ** 2) / (2 * sigma ** 2))
    k = np.outer(g, g)
    return (k / k.sum()).astype(np.float32)


class SpatialSubtractiveNormalization(TensorModule):
    """Subtract a kernel-weighted local mean
    (reference ``nn/SpatialSubtractiveNormalization.scala``)."""

    def __init__(self, n_input_plane: int = 1, kernel=None):
        super().__init__()
        self.n_input_plane = n_input_plane
        k = np.asarray(kernel, np.float32) if kernel is not None else _gaussian2d(9)
        if k.ndim == 1:
            k = np.outer(k, k)
        k = k / (k.sum() * n_input_plane)
        self.register_buffer("kernel", k)

    def _local_mean(self, input):
        n, h, w, c = input.shape
        kh, kw = self.kernel.shape
        ph, pw = kh // 2, kw // 2
        # Depthwise smoothing conv, then mean over channels; divide by the
        # local coefficient map to correct border effects (reference keeps a
        # precomputed ``coef`` tensor — here it's a conv over ones).
        dk = jnp.tile(self.kernel[:, :, None, None], (1, 1, 1, c))
        smooth = jax.lax.conv_general_dilated(
            input, dk, (1, 1), ((ph, ph), (pw, pw)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=c)
        mean = jnp.sum(smooth, axis=-1, keepdims=True)
        ones = jnp.ones((1, h, w, 1), input.dtype)
        coef = jax.lax.conv_general_dilated(
            ones, jnp.asarray(self.kernel)[:, :, None, None] * self.n_input_plane,
            (1, 1), ((ph, ph), (pw, pw)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return mean / coef

    def update_output(self, input):
        squeeze = input.ndim == 3
        if squeeze:
            input = input[None]
        out = input - self._local_mean(input)
        return out[0] if squeeze else out


class SpatialDivisiveNormalization(TensorModule):
    """Divide by the local standard deviation
    (reference ``nn/SpatialDivisiveNormalization.scala``)."""

    def __init__(self, n_input_plane: int = 1, kernel=None,
                 threshold: float = 1e-4, thresval: float = 1e-4):
        super().__init__()
        self.sub = SpatialSubtractiveNormalization(n_input_plane, kernel)
        self.threshold, self.thresval = threshold, thresval

    def update_output(self, input):
        squeeze = input.ndim == 3
        if squeeze:
            input = input[None]
        local_sq_mean = self.sub._local_mean(input * input)
        stdev = jnp.sqrt(jnp.maximum(local_sq_mean, 0.0))
        stdev = jnp.where(stdev < self.threshold, self.thresval, stdev)
        out = input / stdev
        return out[0] if squeeze else out


class SpatialContrastiveNormalization(TensorModule):
    """Subtractive then divisive normalization
    (reference ``nn/SpatialContrastiveNormalization.scala``)."""

    def __init__(self, n_input_plane: int = 1, kernel=None,
                 threshold: float = 1e-4, thresval: float = 1e-4):
        super().__init__()
        self.sub_norm = SpatialSubtractiveNormalization(n_input_plane, kernel)
        self.div_norm = SpatialDivisiveNormalization(n_input_plane, kernel,
                                                     threshold, thresval)

    def update_output(self, input):
        return self.div_norm.update_output(self.sub_norm.update_output(input))
