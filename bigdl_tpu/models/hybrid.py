"""Causal LM over a pattern-built decoder (``nn.HybridDecoder``): the hybrid
Mamba-2 / attention / mixture-of-experts family, the decoders that mix
sliding-window with full attention layers over dense and expert
feed-forwards (their experts routed from the block's own input, ``E``, or
from the layer's, ahead of its attention, ``R``), and those of latent
attention with a multi-token-prediction module. Same shape as
``models.transformer.build_lm`` — embedding, decoder, fused-CE head —
with the per-layer pattern in place of one repeated block and no positional module (state-space layers carry order;
an attention group that rotates says so itself, ``rope=True``).
"""

from __future__ import annotations

from typing import Optional

from bigdl_tpu import nn


class _LM(nn.Sequential):
    """The chain embedding -> decoder -> head of a stack whose expert
    layers pick by token id (``MoE(pick_rows=)``): it runs under
    ``parallel.expert.token_ids``."""

    def update_output(self, input):
        from bigdl_tpu.parallel.expert import token_ids
        with token_ids(input):
            return super().update_output(input)


class _LMWithMTP(_LM):
    """The chain embedding -> decoder -> head with a multi-token-prediction
    module (``nn.MTPModule``, the child ``mtp``) beside it. In training the
    fused-CE Table the head emits gains the module's stream and its loss
    weight (``mtp``, ``mtp_weight``), for ``nn.FusedLMHeadCriterion`` to
    put through the same head against the token after next; in eval the
    module does not run and the output is the chain's. The main stack runs
    under ``token_ids`` of the input, the module under those one position
    on (the token whose embedding its position takes in; the last
    position, which no loss reads, gets the first token's)."""

    def update_output(self, input):
        import jax.numpy as jnp
        from bigdl_tpu.parallel.expert import token_ids
        if not self.training:
            return super().update_output(input)
        *front, decoder, head = self._ordered
        x = input
        for m in front:
            x = m.forward(x)
        with token_ids(input):
            stream = decoder.stream(x)
        out = head.forward(decoder.final_norm.forward(stream))
        with token_ids(jnp.roll(input, -1, axis=-1)):
            out["mtp"] = self.mtp.forward((x, stream))
        out["mtp_weight"] = self.mtp.loss_weight
        return out


class _LoopedLM(nn.Sequential):
    """The chain embedding -> looped decoder -> head
    (``nn.HybridDecoder(passes=P)``) with a learned EXIT GATE beside it
    (the child ``exit_gate``, ONE ``Linear(embed_dim, 1)`` with a bias
    shared by the passes). In training the head is handed all P normed
    streams, stacked (P, B, T, E), and the fused-CE Table it emits gains
    the gate's logit of every pass and position (``exit_logits``, (P, B,
    T), float32, under the scope ``loop_exit``) and the weight of the exit
    distribution's entropy in the loss (``exit_beta``):
    ``nn.FusedLMHeadCriterion`` forms the distribution and the loss from
    them. In eval the gate does not run and the output is the chain's: the
    LAST pass's log-probs (no pass is left early)."""

    def update_output(self, input):
        import jax
        import jax.numpy as jnp
        if not self.training:
            return super().update_output(input)
        *front, decoder, head = self._ordered
        x = input
        for m in front:
            x = m.forward(x)
        streams = decoder.pass_streams(x)
        out = head.forward(streams)
        gate = self.exit_gate
        with jax.named_scope("loop_exit"):
            # the gate's one row against every stream, summed in float32
            out["exit_logits"] = jnp.einsum(
                "...e,e->...", streams, gate.weight[0].astype(streams.dtype),
                preferred_element_type=jnp.float32) \
                + gate.bias[0].astype(jnp.float32)
        out["exit_beta"] = self.exit_beta
        return out


def build_hybrid_lm(vocab_size: int, embed_dim: int, pattern: str,
                    mamba: Optional[dict] = None, moe: Optional[dict] = None,
                    attention: Optional[dict] = None,
                    norm_eps: float = 1e-5,
                    window_attention: Optional[dict] = None,
                    mlp: Optional[dict] = None, post_norm: bool = False,
                    embed_scale: Optional[float] = None,
                    latent_attention: Optional[dict] = None,
                    mtp: Optional[dict] = None,
                    short_conv: Optional[dict] = None,
                    tie_embeddings: bool = False,
                    delta: Optional[dict] = None,
                    pre_norm: bool = True, passes: int = 1,
                    exit_gate: bool = False,
                    exit_beta: float = 0.1) -> nn.Sequential:
    """Causal LM over ``nn.HybridDecoder``, head untied or tied
    (``tie_embeddings``): 1-based token ids (N, T) -> the fused-CE tail.

    Train with ``nn.FusedLMHeadCriterion``; eval/predict see log-probs
    (N, T, vocab).
    ``vocab_size`` may be this chip's slice of a sharded vocabulary: the
    embedding, the head and the loss are then over the slice. The head is
    its own matrix unless ``tie_embeddings``: then ONE (vocab_size,
    embed_dim) matrix serves the lookup and the fused CE
    (``nn.TiedLMHead``, as ``models.transformer.build_lm`` ties it) and
    its gradient is the sum of both uses. Expert
    layers that pick by token id (``moe={"pick_rows": vocab_size, ...}``)
    are told the ids of the stream.
    ``embed_scale`` multiplies the embedding's rows on their way into the
    stack (``sqrt(embed_dim)`` in the families that scale it).
    ``delta`` builds the ``D`` blocks (``nn.GatedDeltaNet``); ``post_norm``
    with ``pre_norm=False`` gives the blocks that norm their mixer's
    output alone (``nn/hybrid.py``).

    ``mtp`` (``{"loss_weight": w}``) adds ONE multi-token-prediction module
    (``nn.MTPModule``): two norms, a (2E -> E) projection, one more layer of
    the pattern's last two kinds (its mixer and its feed-forward, built
    from the same keyword groups) and a norm in front of the head. It has
    no embedding and no head of its own: it reads the model's lookup table
    and its stream goes through the model's ``LMHead``. In training the
    criterion returns ``L_main + w * L_mtp``; eval is the main model's.

    ``passes`` P > 1 LOOPS the stack (``nn.HybridDecoder(passes=)``: every
    block P times over the same parameters, the final norm between passes,
    ONE traced body under ``lax.scan``). Alone, the model trains and
    predicts on the last pass. With ``exit_gate`` it gains ONE
    ``Linear(embed_dim, 1)`` with a bias, shared by the passes, and trains
    on every pass: with ``lam_t = sigmoid(gate(h_t))`` the exit
    distribution of a token is ``p_t = lam_t prod_{j<t}(1 - lam_j)``, the
    last pass taking what is left, and the
    loss ``mean_i[sum_t p_{i,t} CE_{i,t} - exit_beta * H(p_i)]``, ONE fused
    pass over the P x T rows of one head (``nn.FusedLMHeadCriterion``;
    the looped language model of arXiv:2510.25741, its first-stage
    objective). Eval is the last pass's log-probs."""
    if mtp is not None and (passes > 1 or exit_gate):
        raise ValueError("a multi-token-prediction module over a looped "
                         "stack is not built")
    groups = dict(mamba=mamba, moe=moe, attention=attention,
                  norm_eps=norm_eps, window_attention=window_attention,
                  mlp=mlp, post_norm=post_norm,
                  latent_attention=latent_attention, short_conv=short_conv,
                  delta=delta, pre_norm=pre_norm)
    if mtp is not None:
        m = _LMWithMTP()
    elif exit_gate:
        if (moe or {}).get("pick_rows"):
            raise ValueError("experts that pick by token id under an exit "
                             "gate are not built")
        m = _LoopedLM()
    else:
        m = _LM() if (moe or {}).get("pick_rows") else nn.Sequential()
    embed = nn.LookupTable(vocab_size, embed_dim)
    m.add(embed)
    if embed_scale is not None:
        m.add(nn.MulConstant(float(embed_scale)))
    m.add(nn.HybridDecoder(pattern, embed_dim, **groups, passes=passes))
    m.add(nn.TiedLMHead(embed) if tie_embeddings
          else nn.LMHead(embed_dim, vocab_size, with_bias=False))
    if mtp is not None:     # after the chain: modules() in forward order
        m.mtp = nn.MTPModule(
            embed_dim, nn.HybridDecoder(pattern[-2:], embed_dim, **groups),
            norm_eps, **mtp)
    if exit_gate:           # as the module above: after the chain
        m.exit_gate = nn.Linear(embed_dim, 1)
        m.exit_beta = float(exit_beta)
    return m
