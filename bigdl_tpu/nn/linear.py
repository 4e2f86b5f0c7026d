"""Linear-algebra layers (reference ``nn/Linear.scala:43``, ``Bilinear``,
``Cosine``, ``Euclidean``, ``MM``/``MV``, ``LookupTable`` and the
element-scale parameter layers ``Add/CAdd/Mul/CMul/Scale``).

Weight layouts keep Torch conventions ((out, in) for Linear) for import
compatibility; XLA's dot_general makes the transpose free on the MXU.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn import initialization as init
from bigdl_tpu.nn.module import TensorModule, Module
from bigdl_tpu.ops.precision import match_compute
from bigdl_tpu.utils.rng import RandomGenerator


class Linear(TensorModule):
    """Affine map y = xW^T + b (reference ``nn/Linear.scala:43``).

    On TPU this is a single MXU dot; the reference's gemm + rank-1 bias update
    (``Linear.scala`` addmm/addr) fuses into one HLO.
    """

    def __init__(self, input_size: int, output_size: int,
                 with_bias: bool = True,
                 w_regularizer=None, b_regularizer=None):
        super().__init__()
        self.input_size = input_size
        self.output_size = output_size
        self.with_bias = with_bias
        self.register_parameter(
            "weight", init.default_init((output_size, input_size), input_size),
            regularizer=w_regularizer)
        if with_bias:
            self.register_parameter(
                "bias", init.default_init((output_size,), input_size),
                regularizer=b_regularizer)

    def reset(self):
        self.weight = jnp.asarray(
            init.default_init((self.output_size, self.input_size), self.input_size))
        if self.with_bias:
            self.bias = jnp.asarray(
                init.default_init((self.output_size,), self.input_size))

    def update_output(self, input):
        y = jnp.matmul(match_compute(input, self.weight), self.weight.T)
        if self.with_bias:
            y = y + self.bias
        return y

    def __repr__(self):
        return f"Linear({self.input_size} -> {self.output_size})"


class Bilinear(Module):
    """y_k = x1^T W_k x2 + b_k over a Table {x1, x2}
    (reference ``nn/Bilinear.scala:237``)."""

    def __init__(self, input_size1: int, input_size2: int, output_size: int,
                 bias_res: bool = True):
        super().__init__()
        self.bias_res = bias_res
        fan_in = input_size1 * input_size2
        self.register_parameter(
            "weight", init.default_init((output_size, input_size1, input_size2), fan_in))
        if bias_res:
            self.register_parameter("bias", init.default_init((output_size,), fan_in))

    def update_output(self, input):
        x1, x2 = input[1], input[2]
        # (N,I1) x (O,I1,I2) x (N,I2) -> (N,O)
        y = jnp.einsum("ni,oij,nj->no", x1, self.weight, x2)
        if self.bias_res:
            y = y + self.bias
        return y


class Cosine(TensorModule):
    """Cosine similarity to each weight row (reference ``nn/Cosine.scala``)."""

    def __init__(self, input_size: int, output_size: int):
        super().__init__()
        self.register_parameter(
            "weight", init.default_init((output_size, input_size), input_size))

    def update_output(self, input):
        w = self.weight / jnp.maximum(
            jnp.linalg.norm(self.weight, axis=1, keepdims=True), 1e-12)
        x = input / jnp.maximum(
            jnp.linalg.norm(input, axis=-1, keepdims=True), 1e-12)
        return jnp.matmul(x, w.T)


class Euclidean(TensorModule):
    """Euclidean distance to each weight column (reference ``nn/Euclidean.scala``)."""

    def __init__(self, input_size: int, output_size: int):
        super().__init__()
        self.register_parameter(
            "weight", init.default_init((input_size, output_size), input_size))

    def update_output(self, input):
        # ||x - w_j|| for each output j.
        diff = input[..., :, None] - self.weight  # (N, I, O)
        return jnp.sqrt(jnp.maximum(jnp.sum(diff * diff, axis=-2), 1e-24))


class MM(Module):
    """Batch matrix-matrix product of a Table {A, B}
    (reference ``nn/MM.scala``) — direct MXU batch dot."""

    def __init__(self, trans_a: bool = False, trans_b: bool = False):
        super().__init__()
        self.trans_a, self.trans_b = trans_a, trans_b

    def update_output(self, input):
        a, b = input[1], input[2]
        if self.trans_a:
            a = jnp.swapaxes(a, -1, -2)
        if self.trans_b:
            b = jnp.swapaxes(b, -1, -2)
        return jnp.matmul(a, b)


class MV(Module):
    """Batch matrix-vector product of a Table {M, v} (reference ``nn/MV.scala``)."""

    def __init__(self, trans: bool = False):
        super().__init__()
        self.trans = trans

    def update_output(self, input):
        m, v = input[1], input[2]
        if self.trans:
            m = jnp.swapaxes(m, -1, -2)
        return jnp.einsum("...ij,...j->...i", m, v)


class DotProduct(Module):
    """Row-wise dot product of a Table {x, y} (reference ``nn/DotProduct.scala``)."""

    def update_output(self, input):
        return jnp.sum(input[1] * input[2], axis=-1)


class LookupTable(TensorModule):
    """Embedding lookup with 1-based indices
    (reference ``nn/LookupTable.scala:283``).

    TPU note: implemented as one-hot-free ``jnp.take``; with max-norm the
    renormalised table is computed functionally each step (the reference
    mutates rows in place).
    """

    def __init__(self, n_index: int, n_output: int,
                 padding_value: float = 0.0,
                 max_norm: float = float("inf"),
                 norm_type: float = 2.0,
                 should_scale_grad_by_freq: bool = False):
        super().__init__()
        self.n_index, self.n_output = n_index, n_output
        self.padding_value = padding_value
        self.max_norm = max_norm
        self.norm_type = norm_type
        self.register_parameter(
            "weight",
            RandomGenerator.RNG().normal(0.0, 1.0, (n_index, n_output)).astype(np.float32))

    def update_output(self, input):
        w = self.weight
        if self.max_norm != float("inf"):
            norms = jnp.linalg.norm(w, ord=self.norm_type, axis=1, keepdims=True)
            w = jnp.where(norms > self.max_norm, w * (self.max_norm / norms), w)
        idx = input.astype(jnp.int32) - 1
        out = jnp.take(w, jnp.clip(idx, 0, self.n_index - 1), axis=0)
        if self.padding_value != 0:
            out = jnp.where((input == self.padding_value)[..., None], 0.0, out)
        return out


class Add(TensorModule):
    """Learnable bias add (reference ``nn/Add.scala``)."""

    def __init__(self, input_size: int):
        super().__init__()
        self.register_parameter("bias", init.default_init((input_size,), input_size))

    def update_output(self, input):
        return input + self.bias


class CAdd(TensorModule):
    """Learnable bias of arbitrary broadcastable shape
    (reference ``nn/CAdd.scala:188``)."""

    def __init__(self, size: Sequence[int]):
        super().__init__()
        self.size = tuple(size)
        self.register_parameter("bias", init.zeros(self.size))

    def update_output(self, input):
        return input + self.bias


class Mul(TensorModule):
    """Single learnable scalar gain (reference ``nn/Mul.scala``)."""

    def __init__(self):
        super().__init__()
        self.register_parameter("weight", init.default_init((1,), 1))

    def update_output(self, input):
        return input * self.weight[0]


class CMul(TensorModule):
    """Learnable componentwise gain (reference ``nn/CMul.scala:208``)."""

    def __init__(self, size: Sequence[int]):
        super().__init__()
        self.size = tuple(size)
        n = int(np.prod(self.size))
        self.register_parameter("weight", init.default_init(self.size, n))

    def update_output(self, input):
        return input * self.weight


class Scale(TensorModule):
    """CMul then CAdd (reference ``nn/Scale.scala``)."""

    def __init__(self, size: Sequence[int]):
        super().__init__()
        self.cmul = CMul(size)
        self.cadd = CAdd(size)

    def update_output(self, input):
        return self.cadd.update_output(self.cmul.update_output(input))


class LMHead(Module):
    """Vocabulary projection for the fused-CE language-model tail.

    Replaces ``TimeDistributed(Linear(E, V)) -> LogSoftMax`` when training
    with ``FusedLMHeadCriterion``: in TRAINING mode the output is a Table
    ``(hidden, weight, bias)`` — the criterion computes the cross-entropy
    and its gradients a tile of rows at a time directly from the hidden
    states, so the (B, S, V) logits never hit HBM (``ops/lm_head_ce.py``).
    In EVAL mode it computes ordinary log-probabilities, so validation
    metrics, ``predict`` and ``models.generate`` see the standard tail.

    Weight layout is Linear's (V, E); note the parameter TREE path differs
    from the unfused tail (``LMHead.weight`` vs ``TimeDistributed -> Linear
    .weight``), so moving weights between the two tails is an array copy,
    not a tree-structural match.
    """

    _decode = False  # class attr (pickle fwd-compat), see enable_decode

    def __init__(self, input_size: int, vocab_size: int,
                 with_bias: bool = True, w_regularizer=None,
                 b_regularizer=None):
        super().__init__()
        self.input_size = input_size
        self.vocab_size = vocab_size
        self.with_bias = with_bias
        self.register_parameter(
            "weight", init.default_init((vocab_size, input_size), input_size),
            regularizer=w_regularizer)
        if with_bias:
            self.register_parameter(
                "bias", init.default_init((vocab_size,), input_size),
                regularizer=b_regularizer)

    def enable_decode(self) -> "LMHead":
        """Incremental generation: only the LAST position's log-probs are
        computed (sampling never reads the earlier prompt positions, and
        the full (B, S, V) prefill array is exactly what this head exists
        to avoid)."""
        self._decode = True
        return self

    def disable_decode(self) -> "LMHead":
        self._decode = False
        return self

    def update_output(self, input):
        from bigdl_tpu.utils.table import Table
        if self.training:
            if self.with_bias:
                return Table(input, self.weight, self.bias)
            return Table(input, self.weight)
        if self._decode and not getattr(self, "_decode_all", False):
            input = input[:, -1:]
        y = jnp.matmul(match_compute(input, self.weight), self.weight.T)
        if self.with_bias:
            y = y + self.bias
        return jax.nn.log_softmax(y, axis=-1)

    def __repr__(self):
        return f"LMHead({self.input_size} -> {self.vocab_size})"


class TiedLMHead(Module):
    """Vocab projection TIED to the embedding table (GPT-2-style).

    Holds a plain reference (NOT a registered child, so the table appears
    exactly once in the parameter tree, under the LookupTable) and reads
    ``embed.weight`` at forward time. Under ``functional_apply`` that read
    sees the tracer loaded into the embedding, so the loss depends on ONE
    parameter through both uses and autodiff returns the combined
    gradient — tying needs no extra machinery. deepcopy/pickle preserve
    the sharing (both paths to the LookupTable live in one object graph).

    Training mode emits the fused-CE Table ``(hidden, weight)`` (pair with
    ``FusedLMHeadCriterion``); eval mode computes log-probs, slicing to
    the last position while decoding (``models.generate``).
    """

    _decode = False

    def __init__(self, embed: LookupTable):
        super().__init__()
        if embed.max_norm != float("inf"):
            raise ValueError(
                "cannot tie to a max-norm LookupTable: the embedding path "
                "renormalises per forward, so the head would project with "
                "a different matrix than the one that embeds")
        # bypass Module.__setattr__ so the embed is NOT registered as a
        # child module (its weight must stay unique in the parameter tree)
        object.__setattr__(self, "embed_ref", embed)

    def enable_decode(self) -> "TiedLMHead":
        self._decode = True
        return self

    def disable_decode(self) -> "TiedLMHead":
        self._decode = False
        return self

    def update_output(self, input):
        from bigdl_tpu.utils.table import Table
        w = self.embed_ref.weight  # (V, E): the LIVE embedding parameter
        if self.training:
            return Table(input, w)
        if self._decode and not getattr(self, "_decode_all", False):
            input = input[:, -1:]
        y = jnp.matmul(match_compute(input, w), w.T)
        return jax.nn.log_softmax(y, axis=-1)

    def __repr__(self):
        # n_index/n_output avoid dequantizing a quantized table just to
        # print the shape
        return (f"TiedLMHead({self.embed_ref.n_output} -> "
                f"{self.embed_ref.n_index}, tied)")
