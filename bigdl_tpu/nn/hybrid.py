"""Decoder built from a PATTERN of single-mixer blocks: the hybrid
state-space / attention / mixture-of-experts models (Nemotron-H,
Granite-4-H, Jamba) interleave layers of different kinds, where
``TransformerEncoder`` repeats one block.

Every block is pre-norm residual with ONE mixer, ``x <- x + mixer(
RMSNorm(x))``, and the pattern string names each block's mixer by one
character: ``M`` a Mamba-2 layer (``nn.Mamba2``), ``E`` a mixture of
experts (``parallel.expert.MoE``), ``*`` causal self-attention
(``nn.MultiHeadAttention``). The mixers are built from the keyword groups
the caller gives for each kind.
"""

from __future__ import annotations

import jax

from bigdl_tpu.nn.attention import MultiHeadAttention, RMSNorm
from bigdl_tpu.nn.module import Module
from bigdl_tpu.ops.remat import block_remat_policy


class HybridBlock(Module):
    """``x + mixer(norm(x))``."""

    def __init__(self, embed_dim: int, mixer: Module, norm_eps: float):
        super().__init__()
        self.norm = RMSNorm(embed_dim, eps=norm_eps)
        self.mixer = mixer

    def update_output(self, input):
        return input + self.mixer.forward(self.norm.forward(input))


class HybridDecoder(Module):
    """The stack a pattern string describes, with a final RMSNorm.

    ``mamba``, ``moe`` and ``attention`` are the keyword arguments of
    ``nn.Mamba2(embed_dim, ...)``, ``MoE(embed_dim, ...)`` and
    ``nn.MultiHeadAttention(embed_dim, ..., causal=True)``; a kind the
    pattern does not use needs none."""

    KINDS = "ME*"

    #: as ``TransformerEncoder.remat_blocks``: ``Optimizer.set_remat(
    #: "block")`` sets it, and each block then runs under ``jax.checkpoint``
    #: in training, so the backward keeps block boundaries only
    remat_blocks = False

    def __init__(self, pattern: str, embed_dim: int, mamba=None, moe=None,
                 attention=None, norm_eps: float = 1e-5):
        super().__init__()
        bad = set(pattern) - set(self.KINDS)
        if bad or not pattern:
            raise ValueError(f"pattern {pattern!r}: blocks are named by "
                             f"the characters {self.KINDS!r}")
        self.pattern = pattern
        self.num_layers = len(pattern)
        for i, kind in enumerate(pattern):
            if kind == "M":
                from bigdl_tpu.nn.mamba import Mamba2
                mixer = Mamba2(embed_dim, **mamba)
            elif kind == "E":
                from bigdl_tpu.parallel.expert import MoE
                mixer = MoE(embed_dim, **moe)
            else:
                mixer = MultiHeadAttention(embed_dim, causal=True,
                                           **attention)
            self.add_module(f"layer{i}",
                            HybridBlock(embed_dim, mixer, norm_eps))
        self.final_norm = RMSNorm(embed_dim, eps=norm_eps)

    def update_output(self, input):
        x = input
        ckpt = self.remat_blocks and self.training
        for i in range(self.num_layers):
            layer = self._modules[f"layer{i}"]
            if ckpt:
                # a held-expert layer's routed output is kept: its loop
                # over row blocks is not run a second time
                x = jax.checkpoint(lambda h, _l=layer: _l.forward(h),
                                   policy=block_remat_policy())(x)
            else:
                x = layer.forward(x)
        return self.final_norm.forward(x)

    def __repr__(self):
        return f"HybridDecoder({self.pattern!r})"
