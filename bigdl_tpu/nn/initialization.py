"""Weight initialization methods (reference ``nn/InitializationMethod.scala``:
Default, Xavier, BilinearFiller — extended with the usual modern set).

Initialization is host-side numpy driven by the process RandomGenerator, so
model construction is deterministic under ``manual_seed`` and never touches
the accelerator.
"""

from __future__ import annotations

import math
from typing import Sequence

import numpy as np

from bigdl_tpu.utils.rng import RandomGenerator


def default_init(shape: Sequence[int], fan_in: int) -> np.ndarray:
    """Torch default: uniform(-1/sqrt(fanIn), 1/sqrt(fanIn))."""
    stdv = 1.0 / math.sqrt(max(1, fan_in))
    return RandomGenerator.RNG().uniform(-stdv, stdv, tuple(shape)).astype(np.float32)


def xavier(shape: Sequence[int], fan_in: int, fan_out: int) -> np.ndarray:
    """Glorot uniform (reference ``Xavier`` initialization)."""
    stdv = math.sqrt(6.0 / (fan_in + fan_out))
    return RandomGenerator.RNG().uniform(-stdv, stdv, tuple(shape)).astype(np.float32)


def kaiming(shape: Sequence[int], fan_in: int) -> np.ndarray:
    """He-normal, the modern conv default (used by the reference's ResNet
    via MSRinit in ``models/resnet/ResNet.scala``)."""
    std = math.sqrt(2.0 / max(1, fan_in))
    return RandomGenerator.RNG().normal(0.0, std, tuple(shape)).astype(np.float32)


def bilinear_filler(shape: Sequence[int]) -> np.ndarray:
    """Bilinear upsampling kernel for deconvolution
    (reference ``BilinearFiller``, used by ``SpatialFullConvolution``).
    ``shape`` = (kH, kW, in, out)."""
    kh, kw = shape[0], shape[1]
    f_h, f_w = math.ceil(kh / 2.0), math.ceil(kw / 2.0)
    c_h, c_w = (2 * f_h - 1 - f_h % 2) / (2.0 * f_h), (2 * f_w - 1 - f_w % 2) / (2.0 * f_w)
    ys = np.arange(kh)[:, None]
    xs = np.arange(kw)[None, :]
    k = (1 - np.abs(ys / f_h - c_h)) * (1 - np.abs(xs / f_w - c_w))
    out = np.zeros(tuple(shape), dtype=np.float32)
    out[:, :, :, :] = k[:, :, None, None]
    return out


def conv_weight(method: str, shape: Sequence[int], fan_in: int,
                fan_out: int) -> np.ndarray:
    """Conv-weight init dispatch shared by SpatialConvolution and the
    packed stem ("xavier" | "kaiming" | "default")."""
    if method == "xavier":
        return xavier(shape, fan_in, fan_out)
    if method == "kaiming":
        return kaiming(shape, fan_in)
    return default_init(shape, fan_in)


def zeros(shape: Sequence[int]) -> np.ndarray:
    return np.zeros(tuple(shape), dtype=np.float32)


def ones(shape: Sequence[int]) -> np.ndarray:
    return np.ones(tuple(shape), dtype=np.float32)
