"""Int8 weight-only quantized inference — beyond-reference TPU capability.

The reference serves models at fp32 (its ``Predictor``/``Evaluator`` run the
training weights as-is). On TPU, single-stream inference and autoregressive
decoding are WEIGHT-READ bound: every step re-reads all parameters from HBM,
so int8 storage halves the traffic of bf16 (4x fp32) and is the standard
serving trick. This module provides symmetric per-output-channel weight-only
quantization:

- ``q = round(w / s)`` with ``s = amax(|w|, per out-channel) / 127``, stored
  as an int8 BUFFER plus an fp32 scale;
- at use, the weight dequantises to the compute dtype (default bf16) right
  at the matmul — XLA fuses the convert+scale into the dot's operand, so
  HBM sees only int8;
- activations stay bf16/fp32 (weight-only: no calibration data needed, and
  accuracy loss is typically <0.1% top-1 for convnets).

``quantize_model(model)`` deep-copies a trained model and swaps every
supported layer (Linear, LMHead, SpatialConvolution, MultiHeadAttention
projections, LookupTable) for its quantized twin; remaining parametric
layers (LayerNorm, BatchNorm, ...) have their fp32 parameters frozen
into buffers. The original is left untouched; the copy is inference-only
(``parameters()`` is empty across the WHOLE tree — an Optimizer sees
nothing to train).
"""

from __future__ import annotations

from typing import Dict, Type

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.attention import MultiHeadAttention
from bigdl_tpu.nn.conv import SpatialConvolution
from bigdl_tpu.nn.linear import Linear, LMHead, LookupTable, TiedLMHead
from bigdl_tpu.nn.module import Module


def quantize_array(w: jax.Array, channel_axis: int):
    """Symmetric int8 per-channel quantization -> (q int8, scale fp32).

    ``channel_axis`` is the output-channel axis; the scale has w's rank with
    size 1 everywhere else, so ``q * scale`` broadcasts back directly."""
    w = jnp.asarray(w, jnp.float32)
    axes = tuple(a for a in range(w.ndim) if a != channel_axis % w.ndim)
    amax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return q, scale


class _QuantizedMixin:
    """Shared plumbing: move named weight params to int8 buffers."""

    compute_dtype = jnp.bfloat16

    # name -> output-channel axis of that weight
    _quant_weights: Dict[str, int] = {}

    @classmethod
    def _validate(cls, m: Module) -> None:
        """Pre-swap check hook — runs BEFORE the class swap so a rejected
        module is left exactly as it was."""

    def _quantize_in_place(self, compute_dtype):
        self.__dict__["compute_dtype"] = compute_dtype
        for name, axis in self._quant_weights.items():
            w = self._parameters.pop(name)
            q, scale = quantize_array(w, axis)
            self.register_buffer(name + "_q", q)
            self.register_buffer(name + "_scale", scale)
            self._param_regularizers.pop(name, None)
        # biases (and any remaining params) become plain fp32 buffers so the
        # module is invisible to optimizers but still forwards identically
        for name in list(self._parameters):
            self.register_buffer(name, self._parameters.pop(name))

    def _dequant(self, name: str) -> jax.Array:
        cd = self.compute_dtype
        return (self._buffers[name + "_q"].astype(cd)
                * self._buffers[name + "_scale"].astype(cd))

    def reset(self):  # re-init is meaningless on a frozen quantized copy
        raise RuntimeError(f"{type(self).__name__} is inference-only")


class QuantizedLinear(_QuantizedMixin, Linear):
    """Linear with int8 weight + per-output-row scale (inference-only).
    The forward runs the fused int8 Pallas kernel when the tiling fits
    (``ops/int8_matmul.py``): the weight never rematerializes in bf16."""

    _quant_weights = {"weight": 0}  # (out, in)

    weight = property(lambda self: self._dequant("weight"))

    def update_output(self, input):
        from bigdl_tpu.ops.int8_matmul import int8_matmul
        return int8_matmul(
            input, self._buffers["weight_q"], self._buffers["weight_scale"],
            bias=self._buffers["bias"] if self.with_bias else None,
            compute_dtype=self.compute_dtype)


class QuantizedLMHead(_QuantizedMixin, LMHead):
    """LMHead with an int8 vocab projection; eval log-probs only — the
    training-mode Table output would hand the fused criterion a weight
    with no gradient path."""

    _quant_weights = {"weight": 0}  # (V, E)

    weight = property(lambda self: self._dequant("weight"))

    def update_output(self, input):
        if self.training:
            raise RuntimeError("QuantizedLMHead is inference-only; quantize "
                               "after training")
        from bigdl_tpu.ops.int8_matmul import int8_matmul
        if self._decode and not getattr(self, "_decode_all", False):
            input = input[:, -1:]
        y = int8_matmul(
            input, self._buffers["weight_q"], self._buffers["weight_scale"],
            bias=self._buffers["bias"] if self.with_bias else None,
            compute_dtype=self.compute_dtype)
        return jax.nn.log_softmax(y, axis=-1)


class QuantizedSpatialConvolution(_QuantizedMixin, SpatialConvolution):
    """SpatialConvolution with an int8 HWIO kernel + per-output-channel
    scale (inference-only)."""

    _quant_weights = {"weight": -1}  # HWIO: out channel last

    weight = property(lambda self: self._dequant("weight"))


class QuantizedMultiHeadAttention(_QuantizedMixin, MultiHeadAttention):
    """MultiHeadAttention with int8 qkv/out projection weights (per-row
    scales); attention math and KV-cached decode are inherited unchanged.
    The q/k/v/out projections run the fused int8 kernel on raw int8 ROW
    SLICES (per-row scales slice exactly with the rows), so the full
    matrix never rematerializes in bf16."""

    _quant_weights = {"in_proj_weight": 0, "out_proj_weight": 0}

    in_proj_weight = property(lambda self: self._dequant("in_proj_weight"))
    out_proj_weight = property(lambda self: self._dequant("out_proj_weight"))

    def _in_projections(self, query, key, value):
        from bigdl_tpu.ops.int8_matmul import int8_matmul
        e = getattr(self, "_e_q", self.embed_dim)
        ekv = self._e_kv
        wq = self._buffers["in_proj_weight_q"]
        sq = self._buffers["in_proj_weight_scale"]
        bias = (self._buffers["in_proj_bias"]
                if (self.with_bias or getattr(self, "qkv_bias", False))
                else None)
        cd = self.compute_dtype
        # NOT fused into one stacked-matrix call: measured on chip, the
        # single (E+2*Ekv, E) kernel + output slicing is ~10% SLOWER per
        # decode token than three per-slice calls (324 vs 294 us/tok at
        # the 134M config) — the slice kernels cost more than the two
        # saved dispatches
        bq, bk, bv = ((bias[:e], bias[e:e + ekv], bias[e + ekv:])
                      if bias is not None else (None, None, None))
        return (
            int8_matmul(query, wq[:e], sq[:e], bq, cd),
            int8_matmul(key, wq[e:e + ekv], sq[e:e + ekv], bk, cd),
            int8_matmul(value, wq[e + ekv:], sq[e + ekv:], bv, cd),
        )

    def _out_projection(self, ctx):
        from bigdl_tpu.ops.int8_matmul import int8_matmul
        out = int8_matmul(ctx, self._buffers["out_proj_weight_q"],
                          self._buffers["out_proj_weight_scale"],
                          compute_dtype=self.compute_dtype)
        if self.with_bias:
            out = out + self._buffers["out_proj_bias"].astype(
                self.compute_dtype)
        return out


class QuantizedLookupTable(_QuantizedMixin, LookupTable):
    """Embedding: gather int8 ROWS then dequantise — only the touched rows
    are read/converted, and the table itself sits in HBM at 1 byte/entry."""

    _quant_weights = {"weight": 0}  # (vocab, dim): per-row scale

    weight = property(lambda self: self._dequant("weight"))

    @classmethod
    def _validate(cls, m):
        if m.max_norm != float("inf"):
            raise ValueError("max-norm LookupTable cannot be quantized "
                             "(renormalisation needs the fp32 table)")

    def update_output(self, input):
        q = self._buffers["weight_q"]
        scale = self._buffers["weight_scale"]
        idx = jnp.clip(input.astype(jnp.int32) - 1, 0, self.n_index - 1)
        rows = jnp.take(q, idx, axis=0).astype(self.compute_dtype)
        out = rows * jnp.take(scale[:, 0], idx, axis=0)[..., None].astype(
            self.compute_dtype)
        if self.padding_value != 0:
            out = jnp.where((input == self.padding_value)[..., None], 0.0, out)
        return out


class QuantizedTiedLMHead(_QuantizedMixin, TiedLMHead):
    """TiedLMHead over a quantized embedding: the vocab projection runs
    the fused int8 kernel on the table's raw int8 rows instead of
    dequantizing the full (V, E) matrix per forward — the single biggest
    matmul of the decode step, and (empirically, on this toolchain) the
    full-table dequant also pushed large quantized decode programs over a
    Mosaic compiler abort. Inference-only like every quantized twin."""

    _quant_weights = {}  # the tied table lives in the LookupTable

    def update_output(self, input):
        if self.training:
            raise RuntimeError("QuantizedTiedLMHead is inference-only; "
                               "quantize after training")
        embed = self.embed_ref
        if not isinstance(embed, QuantizedLookupTable):
            return super().update_output(input)
        from bigdl_tpu.ops.int8_matmul import int8_matmul
        if self._decode and not getattr(self, "_decode_all", False):
            input = input[:, -1:]
        y = int8_matmul(input, embed._buffers["weight_q"],
                        embed._buffers["weight_scale"],
                        compute_dtype=self.compute_dtype)
        return jax.nn.log_softmax(y, axis=-1)


_REGISTRY: Dict[Type[Module], Type[Module]] = {
    Linear: QuantizedLinear,
    LMHead: QuantizedLMHead,
    SpatialConvolution: QuantizedSpatialConvolution,
    MultiHeadAttention: QuantizedMultiHeadAttention,
    LookupTable: QuantizedLookupTable,
    TiedLMHead: QuantizedTiedLMHead,
}


def quantize_module(m: Module, compute_dtype=jnp.bfloat16) -> Module:
    """In-place class swap + weight quantization of one supported module."""
    qcls = _REGISTRY.get(type(m))
    if qcls is None:
        raise ValueError(f"no quantized twin for {type(m).__name__}")
    qcls._validate(m)  # reject BEFORE mutating: failure leaves m untouched
    m.__class__ = qcls
    m._quantize_in_place(compute_dtype)
    return m


def quantize_model(model: Module, compute_dtype=jnp.bfloat16) -> Module:
    """Deep-copied, int8 weight-only, inference-only twin of ``model``.

    Every EXACT instance of a registry class is swapped (subclasses are
    left alone — they may read weights in ways the twin does not mimic,
    e.g. the fused-kernel conv modules). The copy is returned in eval mode;
    the original is untouched.
    """
    qmodel = model.clone_module()
    for m in qmodel.modules():
        for name, child in list(m._modules.items()):
            if type(child) in _REGISTRY:
                quantize_module(child, compute_dtype)
    if type(qmodel) in _REGISTRY:
        quantize_module(qmodel, compute_dtype)
    # freeze whatever parametric layers remain (norms etc.): fp32 params
    # become buffers, so the whole tree is optimizer-invisible
    for m in qmodel.modules():
        for name in list(m._parameters):
            m.register_buffer(name, m._parameters.pop(name))
        m._param_regularizers.clear()
    return qmodel.evaluate_mode()


def cast_model(model: Module, dtype=jnp.bfloat16) -> Module:
    """Deep-copied inference twin with every float PARAMETER cast to
    ``dtype`` (buffers keep their dtypes — positional tables cast at use).

    The half-precision sibling of ``quantize_model``: B=1 decode at real
    model sizes is WEIGHT-READ-bound (PERF.md round 4: 134M fp32 decodes
    at its 536 MB/read floor), so halving the resident weight bytes
    halves the per-token floor — with bf16's full exponent range, unlike
    int8's scale quantisation. Training must instead use the master-weight
    policy (``Optimizer.set_precision``); the cast twin is eval-only.
    """
    from bigdl_tpu.ops.precision import cast_tree
    twin = model.clone_module()
    for m in twin.modules():
        # params become BUFFERS (the quantize_model freeze): the twin is
        # structurally optimizer-invisible — training a bf16 tree with no
        # fp32 master would silently underflow small updates
        casted = cast_tree(dict(m._parameters), dtype)
        for name in list(m._parameters):
            m._parameters.pop(name)
            m.register_buffer(name, casted[name])
        m._param_regularizers.clear()
    return twin.evaluate_mode()
