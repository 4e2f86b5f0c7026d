"""Double-gated short convolution, the mixer of the LFM2 decoders (Liquid
AI): three of every four layers mix the sequence with a causal depthwise
convolution of a few taps between two elementwise gates, where the fourth
attends. No state beyond the last ``kernel - 1`` gated inputs, no positional
term.

    [B | C | x] = u W_in                         (E -> 3E, split in that order)
    g = B * x
    c_t = sum_j w[:, j] * g_{t - (k - 1) + j}    (depthwise, causal, zeros
                                                 before the start; no bias,
                                                 no activation)
    out = (C * c) W_out                          (E -> E)

Parameter names and layouts follow the public modelling code
(``in_proj_weight`` (3E, E) and ``out_proj_weight`` (E, E) in Linear's
(out, in) layout, ``conv_weight`` (E, k)); as published, nothing carries a
bias.

``causal_depthwise_conv`` is the one causal depthwise convolution of the
package: ``nn.Mamba2`` runs it too, under its own bias and SiLU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bigdl_tpu.nn import initialization as init
from bigdl_tpu.nn.module import TensorModule
from bigdl_tpu.ops.precision import match_compute
from bigdl_tpu.ops.remat import SHORT_CONV_IN_PROJ, keep


def causal_depthwise_conv(x, weight):
    """Causal depthwise convolution along the sequence of ``x`` (B, L, C)
    with ``weight`` (C, k): position t reads t-k+1 .. t, zeros before the
    start. Shifted multiply-adds in float32; the float32 sum is returned."""
    k = weight.shape[-1]
    length = x.shape[1]
    w = weight.astype(jnp.float32)
    padded = jnp.pad(x.astype(jnp.float32), [(0, 0), (k - 1, 0), (0, 0)])
    return sum(padded[:, j:j + length] * w[:, j] for j in range(k))


class ShortConv(TensorModule):
    """Double-gated short convolution, ``(C * conv_k(B * x)) W_out`` with
    ``[B | C | x] = u W_in``: input (B, L, E) -> (B, L, E).

    Training/prefill form only: the whole sequence from an empty
    history."""

    def __init__(self, embed_dim: int, kernel: int = 3):
        super().__init__()
        if kernel < 1:
            raise ValueError(f"kernel must be >= 1, got {kernel}")
        self.embed_dim, self.kernel = embed_dim, kernel
        self.register_parameter("in_proj_weight",
                                init.default_init((3 * embed_dim, embed_dim),
                                                  embed_dim))
        self.register_parameter("conv_weight",
                                init.default_init((embed_dim, kernel),
                                                  kernel))
        self.register_parameter("out_proj_weight",
                                init.default_init((embed_dim, embed_dim),
                                                  embed_dim))

    def _local(self, bcx):
        """What is no projection, from the in-projection's output
        ``[B | C | x]``: the split, the gate ``B * x``, the convolution and
        the gate ``C * c``, in float32."""
        e = self.embed_dim
        b, c, x = (bcx[..., i * e:(i + 1) * e].astype(jnp.float32)
                   for i in range(3))
        return (c * causal_depthwise_conv(b * x, self.conv_weight)
                ).astype(bcx.dtype)

    def update_output(self, input):
        from bigdl_tpu.telemetry import get_registry, instruments
        # trace-time count, as bigdl_ssd_scan_total: the form the local
        # part took (``xla`` is the only one there is)
        instruments(get_registry()).short_conv_total.labels(form="xla").inc()
        # two leaf scopes, as ``nn.Mamba2``'s: the two products under
        # ``short_conv_proj``, the rest under ``short_conv_local``
        # (telemetry/catalogue.SCOPE_SPECS)
        with jax.named_scope("short_conv_proj"):
            w_in = self.in_proj_weight
            # kept across a block's rematerialisation (ops.remat): the
            # wider product runs once; the gates and the convolution twice
            bcx = keep(jnp.matmul(match_compute(input, w_in), w_in.T),
                       SHORT_CONV_IN_PROJ)
        with jax.named_scope("short_conv_local"):
            y = self._local(bcx)
        with jax.named_scope("short_conv_proj"):
            w_out = self.out_proj_weight
            return jnp.matmul(match_compute(y, w_out), w_out.T)

    def __repr__(self):
        return f"ShortConv({self.embed_dim}, kernel={self.kernel})"
