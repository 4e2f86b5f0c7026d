"""Mamba-2 mixer (Dao & Gu 2024), the state-space layer of the hybrid
decoders (Nemotron-H, Granite-4-H, Zamba2): no attention, no positional
term, a per-head recurrent state of ``head_dim x state_size``.

    [z | xBC | dt] = u W_in                      (d_inner | conv_dim | H)
    xBC = silu(causal_depthwise_conv(xBC, k) + b)
    x, B, C = split(xBC)                         (H x P | G x N | G x N)
    dt = softplus(dt + dt_bias);  a = -exp(A_log)
    y = ssd_scan(x, dt, a, B, C) + D * x          (ops/ssd_scan.py: Mosaic
                                                 kernels on a TPU at shapes
                                                 they tile, else XLA)
    y = GroupRMSNorm_G(y * silu(z)) * w
    out = y W_out

Two forms of what lies between the two products, chosen by what
``update_output`` can see (``ops.mamba_local.takes_kernel``: backend, dtype,
shapes) and by nothing else, counted by ``bigdl_mamba_local_total{form}``:

- ``form="kernel"``: on a TPU, bf16, where ``G * N`` is whole 128-lane tiles
  that divide ``d_inner``, a norm group is whole lane tiles and 128 divides
  the length (the Nemotron cell: 4,096 / 1,024 / 512 over 8,192 tokens and
  its reference check's 1,024). ``ops.mamba_local.around_scan``: the
  convolution, bias, SiLU and split in one Mosaic call that reads its columns
  of ``zxbcdt`` where they lie and writes x, B and C; the D skip, gate, group
  norm and weight in another; their backwards in two more behind ONE
  ``custom_vjp`` around the scan, so ``zxbcdt``'s cotangent is one buffer
  written once.
- ``form="xla"``: everything else, a CPU and the tier-1 sizes (an inner
  width of 32) included: the ``jax.numpy`` lines of ``_around_scan``, which
  XLA fuses.

Parameter names and layouts follow the public modelling code
(``in_proj_weight`` (d_in_proj, E) and ``out_proj_weight`` (E, d_inner) in
Linear's (out, in) layout, ``conv_weight`` (conv_dim, k)). As in every
published configuration of the family, the projections carry no bias and
the convolution does.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from bigdl_tpu.nn import initialization as init
from bigdl_tpu.nn.module import TensorModule
from bigdl_tpu.nn.short_conv import causal_depthwise_conv
from bigdl_tpu.ops import mamba_local
from bigdl_tpu.ops.precision import match_compute
from bigdl_tpu.ops.remat import MAMBA_IN_PROJ, keep
from bigdl_tpu.ops.ssd_scan import ssd_scan
from bigdl_tpu.utils.rng import RandomGenerator


def decay_init(rng, num_heads: int, dt_min: float = 1e-3,
               dt_max: float = 0.1, dt_floor: float = 1e-4):
    """(``dt_bias``, ``A_log``) of ``num_heads`` heads as the family
    initialises them: dt log-uniform in [dt_min, dt_max], floored, stored
    through softplus' inverse; A uniform in [1, 16]. ``nn.GatedDeltaNet``
    takes its decay's parameters from here too."""
    dt = np.exp(rng.uniform(np.log(dt_min), np.log(dt_max), (num_heads,)))
    dt = np.maximum(dt, dt_floor)
    return ((dt + np.log(-np.expm1(-dt))).astype(np.float32),
            np.log(rng.uniform(1.0, 16.0, (num_heads,))).astype(np.float32))


class Mamba2(TensorModule):
    """Input (B, L, E) -> (B, L, E). Training/prefill form only: the whole
    sequence through the chunked scan from a zero state."""

    def __init__(self, embed_dim: int, num_heads: int, head_dim: int,
                 state_size: int, n_groups: int = 1, conv_kernel: int = 4,
                 chunk_size: int = 128, norm_eps: float = 1e-5,
                 dt_min: float = 1e-3, dt_max: float = 0.1,
                 dt_floor: float = 1e-4):
        super().__init__()
        if num_heads % n_groups:
            raise ValueError(f"n_groups {n_groups} must divide num_heads "
                             f"{num_heads}")
        self.embed_dim, self.num_heads, self.head_dim = \
            embed_dim, num_heads, head_dim
        self.state_size, self.n_groups = state_size, n_groups
        self.conv_kernel, self.chunk_size = conv_kernel, chunk_size
        self.norm_eps = norm_eps
        d_inner = self.d_inner = num_heads * head_dim
        conv_dim = self.conv_dim = d_inner + 2 * n_groups * state_size
        d_in = d_inner + conv_dim + num_heads
        rng = RandomGenerator.RNG()
        self.register_parameter("in_proj_weight",
                                init.default_init((d_in, embed_dim),
                                                  embed_dim))
        self.register_parameter("conv_weight",
                                init.default_init((conv_dim, conv_kernel),
                                                  conv_kernel))
        self.register_parameter("conv_bias",
                                init.default_init((conv_dim,), conv_kernel))
        # the family's own decay (``decay_init``); D = 1
        dt_bias, a_log = decay_init(rng, num_heads, dt_min, dt_max, dt_floor)
        self.register_parameter("dt_bias", dt_bias)
        self.register_parameter("A_log", a_log)
        self.register_parameter("D", init.ones((num_heads,)))
        self.register_parameter("norm_weight", init.ones((d_inner,)))
        self.register_parameter("out_proj_weight",
                                init.default_init((embed_dim, d_inner),
                                                  d_inner))

    def _conv(self, xbc):
        """The causal depthwise convolution (``nn.short_conv``'s: position
        t reads t-k+1 .. t, zeros before the start) under the family's bias
        and SiLU."""
        out = causal_depthwise_conv(xbc, self.conv_weight) \
            + self.conv_bias.astype(jnp.float32)
        return jax.nn.silu(out).astype(xbc.dtype)

    def update_output(self, input):
        from bigdl_tpu.telemetry import get_registry, instruments
        p, g, n = self.head_dim, self.n_groups, self.state_size
        # three leaf scopes beside the scan's own (``ssd_scan``): the two
        # products under ``mamba_proj``, everything else under
        # ``mamba_local`` (telemetry/catalogue.SCOPE_SPECS)
        with jax.named_scope("mamba_proj"):
            w_in = self.in_proj_weight
            # kept across a block's rematerialisation (ops.remat): the
            # widest product of the block runs once; conv, softplus, gate
            # and scan twice
            zxbcdt = keep(jnp.matmul(match_compute(input, w_in), w_in.T),
                          MAMBA_IN_PROJ)
        # trace-time count, as bigdl_ssd_scan_total: the form the local
        # part took (ops.mamba_local.takes_kernel; PERF.md section 6, PR 43)
        kernel = mamba_local.takes_kernel(
            jax.default_backend(), zxbcdt.dtype, input.shape[1],
            self.d_inner, g * n, g, self.conv_kernel)
        instruments(get_registry()).mamba_local_total.labels(
            form="kernel" if kernel else "xla").inc()
        if kernel:
            with jax.named_scope("mamba_local"):
                y = mamba_local.around_scan(
                    zxbcdt, self.conv_weight, self.conv_bias, self.dt_bias,
                    self.A_log, self.D, self.norm_weight, head_dim=p,
                    n_groups=g, state_size=n, chunk=self.chunk_size,
                    eps=self.norm_eps)
        else:
            y = self._around_scan(zxbcdt, input.dtype)
        with jax.named_scope("mamba_proj"):
            w_out = self.out_proj_weight
            return jnp.matmul(match_compute(y, w_out), w_out.T)

    def _around_scan(self, zxbcdt, dtype):
        """The XLA form of what lies between the projections: these
        ``jax.numpy`` lines, which XLA fuses, around ``ssd_scan``."""
        h, p, g, n = (self.num_heads, self.head_dim, self.n_groups,
                      self.state_size)
        d_inner, conv_dim = self.d_inner, self.conv_dim
        bsz, length, _ = zxbcdt.shape
        with jax.named_scope("mamba_local"):
            z = zxbcdt[..., :d_inner]
            xbc = self._conv(zxbcdt[..., d_inner:d_inner + conv_dim])
            dt = zxbcdt[..., d_inner + conv_dim:]
            x = xbc[..., :d_inner].reshape(bsz, length, h, p)
            b = xbc[..., d_inner:d_inner + g * n].reshape(bsz, length, g, n)
            c = xbc[..., d_inner + g * n:].reshape(bsz, length, g, n)
            dt = jax.nn.softplus(dt.astype(jnp.float32)
                                 + self.dt_bias.astype(jnp.float32))
            a = -jnp.exp(self.A_log.astype(jnp.float32))
        y = ssd_scan(x, dt, a, b, c, self.chunk_size)
        with jax.named_scope("mamba_local"):
            y = y.astype(jnp.float32) + (self.D.astype(jnp.float32)[:, None]
                                         * x.astype(jnp.float32))
            # gated RMSNorm over each of the G groups of the inner width
            y = y.reshape(bsz, length, d_inner) \
                * jax.nn.silu(z.astype(jnp.float32))
            yg = y.reshape(bsz, length, g, d_inner // g)
            yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), axis=-1,
                                             keepdims=True) + self.norm_eps)
            return yg.reshape(bsz, length, d_inner).astype(dtype) \
                * self.norm_weight

    def __repr__(self):
        return (f"Mamba2({self.embed_dim}, heads={self.num_heads}x"
                f"{self.head_dim}, state={self.state_size}, "
                f"groups={self.n_groups})")
