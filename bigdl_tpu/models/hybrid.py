"""Causal LM over a pattern-built decoder (``nn.HybridDecoder``): the hybrid
Mamba-2 / attention / mixture-of-experts family, and the decoders that mix
sliding-window with full attention layers over dense and expert
feed-forwards. Same shape as ``models.transformer.build_lm`` — embedding,
decoder, fused-CE head — with the per-layer pattern in place of one
repeated block and no positional module (state-space layers carry order;
an attention group that rotates says so itself, ``rope=True``).
"""

from __future__ import annotations

from typing import Optional

from bigdl_tpu import nn


def build_hybrid_lm(vocab_size: int, embed_dim: int, pattern: str,
                    mamba: Optional[dict] = None, moe: Optional[dict] = None,
                    attention: Optional[dict] = None,
                    norm_eps: float = 1e-5,
                    window_attention: Optional[dict] = None,
                    mlp: Optional[dict] = None, post_norm: bool = False,
                    embed_scale: Optional[float] = None) -> nn.Sequential:
    """1-based token ids (N, T) -> the fused-CE tail: train with
    ``nn.FusedLMHeadCriterion``; eval/predict see log-probs (N, T, vocab).
    ``vocab_size`` may be this chip's slice of a sharded vocabulary: the
    embedding, the head and the loss are then over the slice. The head is
    its own matrix (the family does not tie it to the embedding).
    ``embed_scale`` multiplies the embedding's rows on their way into the
    stack (``sqrt(embed_dim)`` in the families that scale it)."""
    m = nn.Sequential().add(nn.LookupTable(vocab_size, embed_dim))
    if embed_scale is not None:
        m.add(nn.MulConstant(float(embed_scale)))
    m.add(nn.HybridDecoder(pattern, embed_dim, mamba=mamba, moe=moe,
                           attention=attention, norm_eps=norm_eps,
                           window_attention=window_attention, mlp=mlp,
                           post_norm=post_norm))
    return m.add(nn.LMHead(embed_dim, vocab_size, with_bias=False))
