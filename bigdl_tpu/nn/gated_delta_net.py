"""Gated delta-rule linear attention (Gated DeltaNet, Yang et al.,
arXiv:2412.06464), the mixer of the linear-attention / full-attention
hybrid decoders: three of every four layers keep a per-head state of
``d_k x d_v`` that they decay, correct under the incoming key and write to,
where the fourth attends. No positional term, no cache that grows.

With H heads, ``u`` the block's input (B, L, E)::

    [q | k | v | z | b | a] = u W_in          (H d_k | H d_k | H d_v | H d_v
                                               | H | H)
    [q | k | v] = silu(causal_depthwise_conv([q | k | v], k=4))   (no bias)
    q = q / |q|_2 * d_k^-1/2,  k = k / |k|_2                      (a head)
    beta = sigmoid(b) (x 2 with ``allow_neg_eigval``: the state's
                       transition then has eigenvalues in (-1, 1))
    g = -exp(A_log) * softplus(a + dt_bias)                       (<= 0)
    o = gated_delta_rule(q, k, v, g, beta)     (ops/delta_rule.py: S_t =
                                               exp(g_t) (I - beta_t k_t
                                               k_t^T) S_{t-1} + beta_t k_t
                                               v_t^T, o_t = S_t^T q_t)
    y = RMSNorm_{d_v}(o) * w * silu(z)        (a head; one (d_v,) weight)
    out = y W_out

Parameter names follow ``nn.Mamba2``'s: ``in_proj_weight`` (2 H d_k + 2 H
d_v + 2 H, E) with the six published projections stacked in the order
above and ``out_proj_weight`` (E, H d_v) in Linear's (out, in) layout,
``conv_weight`` (2 H d_k + H d_v, k) over q, k and v side by side,
``A_log`` and ``dt_bias`` (H,) initialised as ``nn.Mamba2``'s are
(``nn.mamba.decay_init``), ``norm_weight`` (d_v,). Nothing carries a bias.

What lies between the two products and is not the recurrence (the scope
``delta_local``) has two forms, chosen INSIDE ``_recurrence_inputs`` and
``_gated_norm`` by what they can see (``ops.delta_local.takes_kernel``:
backend, dtype, shapes) and by nothing else, counted by
``bigdl_delta_local_total{form}``:

- ``kernel``: on a TPU for bf16 operands at the published head sizes (30
  heads of 96 / 192, or the 15 a chip holds). The convolution, SiLU, the
  two L2 norms and q's scale are ONE Mosaic call over the in-projection's
  output where it lies, the gated RMSNorm another, each with its backward
  call behind a ``jax.custom_vjp`` (``ops/delta_local.py``); ``beta`` and
  ``g``, (tokens, heads)-sized, stay these lines.
- ``xla``: the ``jax.numpy`` lines below, everywhere else (a CPU, float32
  operands, tier-1's heads of 8 / 16).

A ``correct`` gate plants its faults by replacing the two methods on the
class, ``gated_delta_rule`` in this module and ``allow_neg_eigval`` on the
instance: ``update_output`` calls all three by those names with the (B, L,
H, d) layouts, whichever form runs.

``num_heads`` may be this chip's share of a layer's heads: the
out-projection then gives its heads' part of the sum that the chips
holding the rest complete, and nothing here spans heads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bigdl_tpu.nn import initialization as init
from bigdl_tpu.nn.mamba import decay_init
from bigdl_tpu.nn.module import TensorModule
from bigdl_tpu.nn.short_conv import causal_depthwise_conv
from bigdl_tpu.ops import delta_local
from bigdl_tpu.ops.delta_rule import gated_delta_rule
from bigdl_tpu.ops.precision import match_compute
from bigdl_tpu.ops.remat import DELTA_IN_PROJ, keep
from bigdl_tpu.utils.rng import RandomGenerator

#: under the root of the two L2 norms (the public kernels' ``l2norm``)
L2_EPS = 1e-6


class GatedDeltaNet(TensorModule):
    """Input (B, L, E) -> (B, L, E). Training/prefill form only: the whole
    sequence through the chunked recurrence from a zero state."""

    def __init__(self, embed_dim: int, num_heads: int, key_head_dim: int,
                 value_head_dim: int, conv_kernel: int = 4,
                 allow_neg_eigval: bool = False, norm_eps: float = 1e-6,
                 chunk_size: int = 64):
        super().__init__()
        self.embed_dim, self.num_heads = embed_dim, num_heads
        self.key_head_dim, self.value_head_dim = key_head_dim, value_head_dim
        self.conv_kernel, self.chunk_size = conv_kernel, chunk_size
        self.allow_neg_eigval, self.norm_eps = allow_neg_eigval, norm_eps
        self.d_key = num_heads * key_head_dim
        self.d_value = num_heads * value_head_dim
        conv_dim = self.conv_dim = 2 * self.d_key + self.d_value
        d_in = conv_dim + self.d_value + 2 * num_heads
        self.register_parameter("in_proj_weight",
                                init.default_init((d_in, embed_dim),
                                                  embed_dim))
        self.register_parameter("conv_weight",
                                init.default_init((conv_dim, conv_kernel),
                                                  conv_kernel))
        dt_bias, a_log = decay_init(RandomGenerator.RNG(), num_heads)
        self.register_parameter("dt_bias", dt_bias)
        self.register_parameter("A_log", a_log)
        self.register_parameter("norm_weight", init.ones((value_head_dim,)))
        self.register_parameter("out_proj_weight",
                                init.default_init((embed_dim, self.d_value),
                                                  self.d_value))

    def _local_kernel(self, dtype, length) -> bool:
        """Whether the local part takes its Mosaic calls
        (``ops.delta_local.takes_kernel``) for operands of ``dtype`` at
        ``length`` positions."""
        return delta_local.takes_kernel(
            jax.default_backend(), dtype, length, self.num_heads,
            self.key_head_dim, self.value_head_dim, self.conv_kernel)

    def _recurrence_inputs(self, proj):
        """From the in-projection's output: ``q``, ``k`` (B, L, H, d_k) and
        ``v`` (B, L, H, d_v) in its dtype as the recurrence reads them
        (convolved, SiLU'd, normalised, ``q`` scaled), ``g`` and ``beta``
        (B, L, H) in float32."""
        h, dk, dv = self.num_heads, self.key_head_dim, self.value_head_dim
        bsz, length, _ = proj.shape
        f32 = jnp.float32
        if self._local_kernel(proj.dtype, length):
            q, k, v = delta_local.conv_silu_norm(
                proj, self.conv_weight, heads=h, d_k=dk, d_v=dv,
                l2_eps=L2_EPS)
        else:
            qkv = jax.nn.silu(causal_depthwise_conv(
                proj[..., :self.conv_dim], self.conv_weight))
            q = qkv[..., :self.d_key].reshape(bsz, length, h, dk)
            k = qkv[..., self.d_key:2 * self.d_key].reshape(bsz, length, h,
                                                            dk)
            v = qkv[..., 2 * self.d_key:].reshape(bsz, length, h, dv)
            q, k = (t * jax.lax.rsqrt(jnp.sum(jnp.square(t), -1,
                                              keepdims=True) + L2_EPS)
                    for t in (q, k))
            q = q * dk ** -0.5
            q, k, v = (t.astype(proj.dtype) for t in (q, k, v))
        b = proj[..., -2 * h:-h].astype(f32)
        a = proj[..., -h:].astype(f32)
        beta = jax.nn.sigmoid(b) * (2.0 if self.allow_neg_eigval else 1.0)
        g = -jnp.exp(self.A_log.astype(f32)) \
            * jax.nn.softplus(a + self.dt_bias.astype(f32))
        return q, k, v, g, beta

    def _gated_norm(self, o, z):
        """``RMSNorm_{d_v}(o) * w * silu(z)`` a head, in float32; ``o``
        (B, L, H, d_v), ``z`` (B, L, H d_v) -> (B, L, H d_v) in ``z``'s
        dtype."""
        if self._local_kernel(z.dtype, z.shape[1]):
            return delta_local.gated_norm(o, z, self.norm_weight,
                                          eps=self.norm_eps)
        o = o.astype(jnp.float32)
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                              + self.norm_eps) \
            * self.norm_weight.astype(jnp.float32)
        return (o.reshape(z.shape)
                * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)

    def update_output(self, input):
        from bigdl_tpu.telemetry import get_registry, instruments
        # trace-time count, as bigdl_ssd_scan_total
        ins = instruments(get_registry())
        ins.gated_delta_net_total.inc()
        # three leaf scopes, as ``nn.Mamba2``'s: the two products under
        # ``delta_proj``, the recurrence under its own ``delta_rule``,
        # everything else under ``delta_local``
        # (telemetry/catalogue.SCOPE_SPECS)
        with jax.named_scope("delta_proj"):
            w_in = self.in_proj_weight
            # kept across a block's rematerialisation (ops.remat): the
            # widest product of the block runs once; the convolution, the
            # norms and the recurrence twice (each in the form it took: the
            # Mosaic calls' forwards run again, not their residuals kept)
            proj = keep(jnp.matmul(match_compute(input, w_in), w_in.T),
                        DELTA_IN_PROJ)
        # the form the local part takes (ops.delta_local.takes_kernel;
        # PERF.md section 6, PR 47)
        ins.delta_local_total.labels(
            form="kernel" if self._local_kernel(proj.dtype, proj.shape[1])
            else "xla").inc()
        with jax.named_scope("delta_local"):
            q, k, v, g, beta = self._recurrence_inputs(proj)
        o = gated_delta_rule(q, k, v, g, beta, self.chunk_size)
        with jax.named_scope("delta_local"):
            z = proj[..., self.conv_dim:self.conv_dim + self.d_value]
            y = self._gated_norm(o, z)
        with jax.named_scope("delta_proj"):
            w_out = self.out_proj_weight
            return jnp.matmul(match_compute(y, w_out), w_out.T)

    def __repr__(self):
        return (f"GatedDeltaNet({self.embed_dim}, heads={self.num_heads}x"
                f"{self.key_head_dim}/{self.value_head_dim})")
