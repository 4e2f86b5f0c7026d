"""Convolution family (reference ``nn/SpatialConvolution.scala:36`` et al.).

The reference lowers conv to im2col + MKL gemm with hand-parallelised
per-sample tasks (``SpatialConvolution.scala:178-203``, ``NNPrimitive.scala``).
On TPU the whole family is ``lax.conv_general_dilated``, which XLA tiles
directly onto the MXU — so ``SpatialShareConvolution`` (a buffer-sharing
variant) degenerates to an alias, and the im2col/col2im machinery has no
equivalent here by design.

Layout: **channels-last (NHWC / NDHWC)** end-to-end — the TPU-native layout.
Constructor signatures keep the reference's (plane/kernel/stride/pad) order.
Weights are stored HWIO; ``interop.torch`` converts Torch's (G, O/g, I/g, kH,
kW) on import.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn import initialization as init
from bigdl_tpu.nn.module import TensorModule
from bigdl_tpu.ops.precision import match_compute

_DN_2D = ("NHWC", "HWIO", "NHWC")
_DN_3D = ("NDHWC", "DHWIO", "NDHWC")


class SpatialConvolution(TensorModule):
    """2-D convolution (reference ``nn/SpatialConvolution.scala:36``)."""

    def __init__(self, n_input_plane: int, n_output_plane: int,
                 kernel_w: int, kernel_h: int,
                 stride_w: int = 1, stride_h: int = 1,
                 pad_w: int = 0, pad_h: int = 0,
                 n_group: int = 1, propagate_back: bool = True,
                 with_bias: bool = True,
                 w_regularizer=None, b_regularizer=None,
                 init_method: str = "default"):
        super().__init__()
        assert n_input_plane % n_group == 0 and n_output_plane % n_group == 0
        self.n_input_plane, self.n_output_plane = n_input_plane, n_output_plane
        self.kernel_w, self.kernel_h = kernel_w, kernel_h
        self.stride_w, self.stride_h = stride_w, stride_h
        self.pad_w, self.pad_h = pad_w, pad_h
        self.n_group = n_group
        self.propagate_back = propagate_back
        self.with_bias = with_bias
        self.init_method = init_method
        self._init_params(w_regularizer, b_regularizer)

    def _weight_shape(self):
        return (self.kernel_h, self.kernel_w,
                self.n_input_plane // self.n_group, self.n_output_plane)

    def _init_params(self, w_reg=None, b_reg=None):
        fan_in = self.kernel_h * self.kernel_w * self.n_input_plane // self.n_group
        fan_out = self.kernel_h * self.kernel_w * self.n_output_plane // self.n_group
        w = init.conv_weight(self.init_method, self._weight_shape(),
                             fan_in, fan_out)
        self.register_parameter("weight", w, regularizer=w_reg)
        if self.with_bias:
            self.register_parameter("bias", init.default_init((self.n_output_plane,), fan_in),
                                    regularizer=b_reg)

    def reset(self):
        self._init_params()

    def update_output(self, input):
        squeeze = input.ndim == 3
        if squeeze:  # unbatched (H, W, C)
            input = input[None]
        input = match_compute(input, self.weight)
        out = jax.lax.conv_general_dilated(
            input, self.weight,
            window_strides=(self.stride_h, self.stride_w),
            padding=((self.pad_h, self.pad_h), (self.pad_w, self.pad_w)),
            dimension_numbers=_DN_2D,
            feature_group_count=self.n_group)
        if self.with_bias:
            out = out + self.bias
        return out[0] if squeeze else out

    def __repr__(self):
        return (f"SpatialConvolution({self.n_input_plane} -> {self.n_output_plane}, "
                f"{self.kernel_w}x{self.kernel_h}, {self.stride_w},{self.stride_h}, "
                f"{self.pad_w},{self.pad_h})")


class SpatialShareConvolution(SpatialConvolution):
    """reference ``nn/SpatialShareConvolution.scala`` shares im2col buffers
    across replicas to cut memory; under XLA there are no such buffers, so
    this is exactly SpatialConvolution."""


def stem_conv7(n_in: int, n_out: int, with_bias: bool = True,
               init_method: str = "default", name: str = ""):
    """Factory for the 7x7/s2/p3 ImageNet stem: SpaceToDepthConv7, the
    measured-faster packed form. Its parameter schema is the plain
    SpatialConvolution's ("weight" (7,7,C,O) [+ "bias"]), so checkpoints
    interchange."""
    mod = SpaceToDepthConv7(n_in, n_out, with_bias=with_bias,
                            init_method=init_method)
    return mod.set_name(name) if name else mod


class SpaceToDepthConv7(TensorModule):
    """The 7x7/stride-2/pad-3 stem conv computed via 2x2 space-to-depth —
    numerically identical, ~4x better MXU utilisation (the MLPerf ResNet
    trick, here as a drop-in module).

    A (H, W, 3) input drives the MXU at 3/128 lane occupancy; packing 2x2
    pixels into the channel dim gives a (H/2, W/2, 12) input and turns the
    7x7/s2 conv into a 4x4/s1 conv at 4x the input channels. The parameter
    stays the reference-shaped ``(7, 7, C, O)`` tensor ("weight", kaiming —
    checkpoint-compatible with SpatialConvolution); the forward scatters it
    into the packed ``(4, 4, 4C, O)`` layout (pad 7x7 -> 8x8 at offset 1,
    regroup) — a 9 KB transform, so the function class is EXACTLY the
    reference stem, not a freely-trained 8x8 conv.

    Derivation: out(i,j) = sum_{r,s} w7[r,s] x[2i-3+r, 2j-3+s]. With packed
    blocks xp[I] = x[2I:2I+2], a 4-block window starting at I = i-2 covers
    pixels 2i-4 .. 2i+3; embedding w7 at offset 1 in an 8x8 w8 aligns
    w8[kh] with pixel 2i-4+kh = 2i-3+r. Packed padding (2, 1) per side
    reproduces pixel padding (3, 2) (pixel pad 3 lo + the odd window end).
    """

    def __init__(self, n_input_plane: int, n_output_plane: int,
                 with_bias: bool = True, init_method: str = "default",
                 w_regularizer=None, b_regularizer=None):
        super().__init__()
        self.n_input_plane = n_input_plane
        self.n_output_plane = n_output_plane
        self.with_bias = with_bias
        # full SpatialConvolution attribute surface so interop (.t7 export,
        # Caffe import) treats this as the 7x7/s2/p3 conv it is
        self.kernel_h = self.kernel_w = 7
        self.stride_h = self.stride_w = 2
        self.pad_h = self.pad_w = 3
        self.n_group = 1
        fan_in = 7 * 7 * n_input_plane
        fan_out = 7 * 7 * n_output_plane
        w = init.conv_weight(init_method, (7, 7, n_input_plane,
                                           n_output_plane), fan_in, fan_out)
        self.register_parameter("weight", w, regularizer=w_regularizer)
        if with_bias:
            self.register_parameter(
                "bias", init.default_init((n_output_plane,), fan_in),
                regularizer=b_regularizer)

    def update_output(self, input):
        squeeze = input.ndim == 3
        if squeeze:
            input = input[None]
        x = match_compute(input, self.weight)
        if x.shape[-1] != self.n_input_plane:
            raise ValueError(f"SpaceToDepthConv7({self.n_input_plane}) got "
                             f"input {x.shape}")
        # Odd spatial dims: extend with one zero row/col. Exactly equivalent
        # — the appended zeros occupy positions the plain conv's own hi-side
        # padding covered, and the packed output count (H+1)/2 matches the
        # plain conv's (H-1)//2 + 1.
        pad_h, pad_w = x.shape[1] % 2, x.shape[2] % 2
        if pad_h or pad_w:
            x = jnp.pad(x, ((0, 0), (0, pad_h), (0, pad_w), (0, 0)))
        n, h, w, c = x.shape
        o = self.n_output_plane
        # pack 2x2 spatial blocks into channels, order (di, dj, c)
        xp = (x.reshape(n, h // 2, 2, w // 2, 2, c)
              .transpose(0, 1, 3, 2, 4, 5)
              .reshape(n, h // 2, w // 2, 4 * c))
        # scatter the 7x7 weight into the packed 4x4 layout (same order)
        w8 = jnp.pad(self.weight.astype(x.dtype),
                     ((1, 0), (1, 0), (0, 0), (0, 0)))
        w4 = (w8.reshape(4, 2, 4, 2, c, o)
              .transpose(0, 2, 1, 3, 4, 5)
              .reshape(4, 4, 4 * c, o))
        out = jax.lax.conv_general_dilated(
            xp, w4, window_strides=(1, 1), padding=((2, 1), (2, 1)),
            dimension_numbers=_DN_2D)
        if self.with_bias:
            out = out + self.bias
        return out[0] if squeeze else out

    def __repr__(self):
        return (f"SpaceToDepthConv7({self.n_input_plane} -> "
                f"{self.n_output_plane}, 7x7, 2,2, 3,3, space-to-depth)")


class SpatialDilatedConvolution(TensorModule):
    """Atrous conv (reference ``nn/SpatialDilatedConvolution.scala:560``)."""

    def __init__(self, n_input_plane: int, n_output_plane: int,
                 kw: int, kh: int, dw: int = 1, dh: int = 1,
                 pad_w: int = 0, pad_h: int = 0,
                 dilation_w: int = 1, dilation_h: int = 1,
                 w_regularizer=None, b_regularizer=None):
        super().__init__()
        self.n_input_plane, self.n_output_plane = n_input_plane, n_output_plane
        self.kw, self.kh, self.dw, self.dh = kw, kh, dw, dh
        self.pad_w, self.pad_h = pad_w, pad_h
        self.dilation_w, self.dilation_h = dilation_w, dilation_h
        fan_in = kh * kw * n_input_plane
        self.register_parameter("weight",
                                init.default_init((kh, kw, n_input_plane, n_output_plane), fan_in),
                                regularizer=w_regularizer)
        self.register_parameter("bias", init.default_init((n_output_plane,), fan_in),
                                regularizer=b_regularizer)

    def update_output(self, input):
        squeeze = input.ndim == 3
        if squeeze:
            input = input[None]
        out = jax.lax.conv_general_dilated(
            input, self.weight,
            window_strides=(self.dh, self.dw),
            padding=((self.pad_h, self.pad_h), (self.pad_w, self.pad_w)),
            rhs_dilation=(self.dilation_h, self.dilation_w),
            dimension_numbers=_DN_2D)
        out = out + self.bias
        return out[0] if squeeze else out


class SpatialFullConvolution(TensorModule):
    """Transposed (fractionally-strided) convolution, a.k.a. deconvolution
    (reference ``nn/SpatialFullConvolution.scala:790``).

    out = (in - 1)·stride - 2·pad + kernel + adj. Implemented as input-dilated
    conv with a spatially-flipped kernel — the exact transpose of
    SpatialConvolution, so the pair is adjoint like the reference's.
    """

    def __init__(self, n_input_plane: int, n_output_plane: int,
                 kw: int, kh: int, dw: int = 1, dh: int = 1,
                 pad_w: int = 0, pad_h: int = 0,
                 adj_w: int = 0, adj_h: int = 0,
                 n_group: int = 1, no_bias: bool = False,
                 w_regularizer=None, b_regularizer=None):
        super().__init__()
        assert adj_w < dw and adj_h < dh, "adj must be smaller than stride"
        self.n_input_plane, self.n_output_plane = n_input_plane, n_output_plane
        self.kw, self.kh, self.dw, self.dh = kw, kh, dw, dh
        self.pad_w, self.pad_h = pad_w, pad_h
        self.adj_w, self.adj_h = adj_w, adj_h
        self.n_group = n_group
        self.with_bias = not no_bias
        fan_in = kh * kw * n_output_plane // n_group  # deconv fan uses output side
        self.register_parameter(
            "weight",
            init.default_init((kh, kw, n_output_plane // n_group, n_input_plane), fan_in),
            regularizer=w_regularizer)
        if self.with_bias:
            self.register_parameter("bias", init.zeros((n_output_plane,)),
                                    regularizer=b_regularizer)

    def update_output(self, input):
        squeeze = input.ndim == 3
        if squeeze:
            input = input[None]
        # Transpose of a strided conv: dilate the input by stride, pad with
        # (k - 1 - pad) (+ adj on the trailing edge), flip the kernel, and
        # swap its in/out channels.
        w = jnp.flip(self.weight, axis=(0, 1))          # (kh,kw,O/g,I)
        w = jnp.swapaxes(w, 2, 3) if self.n_group == 1 else self._group_swap(w)
        out = jax.lax.conv_general_dilated(
            input, w,
            window_strides=(1, 1),
            padding=((self.kh - 1 - self.pad_h, self.kh - 1 - self.pad_h + self.adj_h),
                     (self.kw - 1 - self.pad_w, self.kw - 1 - self.pad_w + self.adj_w)),
            lhs_dilation=(self.dh, self.dw),
            dimension_numbers=_DN_2D,
            feature_group_count=self.n_group)
        if self.with_bias:
            out = out + self.bias
        return out[0] if squeeze else out

    def _group_swap(self, w):
        # (kh,kw,O/g,I) -> per-group swap to (kh,kw,I/g,O)
        kh, kw = self.kh, self.kw
        g = self.n_group
        og, i = self.n_output_plane // g, self.n_input_plane
        w = jnp.reshape(w, (kh, kw, og, g, i // g))
        w = jnp.transpose(w, (0, 1, 4, 3, 2))
        return jnp.reshape(w, (kh, kw, i // g, self.n_output_plane))


class VolumetricConvolution(TensorModule):
    """3-D convolution (reference ``nn/VolumetricConvolution.scala:340``).
    Layout NDHWC; signature keeps the reference's (kT, kW, kH, ...) order."""

    def __init__(self, n_input_plane: int, n_output_plane: int,
                 k_t: int, k_w: int, k_h: int,
                 d_t: int = 1, d_w: int = 1, d_h: int = 1,
                 pad_t: int = 0, pad_w: int = 0, pad_h: int = 0,
                 with_bias: bool = True):
        super().__init__()
        self.n_input_plane, self.n_output_plane = n_input_plane, n_output_plane
        self.k_t, self.k_w, self.k_h = k_t, k_w, k_h
        self.d_t, self.d_w, self.d_h = d_t, d_w, d_h
        self.pad_t, self.pad_w, self.pad_h = pad_t, pad_w, pad_h
        self.with_bias = with_bias
        fan_in = k_t * k_h * k_w * n_input_plane
        self.register_parameter(
            "weight", init.default_init((k_t, k_h, k_w, n_input_plane, n_output_plane), fan_in))
        if with_bias:
            self.register_parameter("bias", init.default_init((n_output_plane,), fan_in))

    def update_output(self, input):
        squeeze = input.ndim == 4
        if squeeze:
            input = input[None]
        out = jax.lax.conv_general_dilated(
            input, self.weight,
            window_strides=(self.d_t, self.d_h, self.d_w),
            padding=((self.pad_t, self.pad_t), (self.pad_h, self.pad_h),
                     (self.pad_w, self.pad_w)),
            dimension_numbers=_DN_3D)
        if self.with_bias:
            out = out + self.bias
        return out[0] if squeeze else out


class SpatialConvolutionMap(TensorModule):
    """Convolution with an explicit input→output connection table
    (reference ``nn/SpatialConvolutionMap.scala:366``).

    ``conn_table`` is an (nPairs, 2) array of 1-based (inPlane, outPlane)
    pairs. TPU-native realisation: a dense conv whose kernel is masked to the
    table's sparsity — one MXU conv beats gather/scatter loops.
    """

    def __init__(self, conn_table, kernel_w: int, kernel_h: int,
                 stride_w: int = 1, stride_h: int = 1,
                 pad_w: int = 0, pad_h: int = 0):
        super().__init__()
        conn = np.asarray(conn_table, dtype=np.int64)
        self.n_input_plane = int(conn[:, 0].max())
        self.n_output_plane = int(conn[:, 1].max())
        self.kernel_w, self.kernel_h = kernel_w, kernel_h
        self.stride_w, self.stride_h = stride_w, stride_h
        self.pad_w, self.pad_h = pad_w, pad_h
        mask = np.zeros((self.n_input_plane, self.n_output_plane), np.float32)
        mask[conn[:, 0] - 1, conn[:, 1] - 1] = 1.0
        self.register_buffer("mask", mask[None, None])
        fan_in = int(conn.shape[0] / self.n_output_plane * kernel_w * kernel_h)
        self.register_parameter(
            "weight",
            init.default_init((kernel_h, kernel_w, self.n_input_plane, self.n_output_plane),
                              max(1, fan_in)))
        self.register_parameter("bias", init.default_init((self.n_output_plane,),
                                                          max(1, fan_in)))

    @staticmethod
    def full(n_in: int, n_out: int):
        return np.stack(np.meshgrid(np.arange(1, n_in + 1),
                                    np.arange(1, n_out + 1)), -1).reshape(-1, 2)

    @staticmethod
    def one_to_one(n_features: int):
        idx = np.arange(1, n_features + 1)
        return np.stack([idx, idx], axis=1)

    @staticmethod
    def random(n_in: int, n_out: int, n_to: int):
        from bigdl_tpu.utils.rng import RandomGenerator
        rng = RandomGenerator.RNG()
        pairs = []
        for o in range(1, n_out + 1):
            ins = rng.randperm(n_in)[:n_to]
            pairs.extend((int(i), o) for i in ins)
        return np.asarray(pairs)

    def update_output(self, input):
        squeeze = input.ndim == 3
        if squeeze:
            input = input[None]
        out = jax.lax.conv_general_dilated(
            input, self.weight * self.mask,
            window_strides=(self.stride_h, self.stride_w),
            padding=((self.pad_h, self.pad_h), (self.pad_w, self.pad_w)),
            dimension_numbers=_DN_2D)
        out = out + self.bias
        return out[0] if squeeze else out
