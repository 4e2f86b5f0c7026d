"""Operations and bytes of the LFM2 mixture-of-experts decoder LM
(double-gated short-convolution and full GQA layers over a dense and
sigmoid-routed SwiGLU feed-forwards, a head tied to the embedding) from
shapes, as ``flops.py`` counts the dense decoder: what the algorithm needs,
forward + backward = 3 x forward for every matrix product, recomputation
(block remat, flash's backward) NOT counted, elementwise work left out of
the model's count, a multiply-add 2 FLOPs.

The convolution mixer's products are 8 E^2 a token forward (E -> 3E in, E
-> E out: 33.5 MFLOP at E = 2,048); its local part (the split, the two
gates, the k-tap depthwise convolution) is bound by BYTES and has a cost of
its own (``short_conv_local_cost``) for ``short_conv_local_roofline``.
Attention is counted at the causal half's query-key pairs with the
diagonal. The routed experts are counted at the picks a BALANCED router
lands on the experts HELD here, ``tokens * num_experts_per_tok * held /
router width`` (16,384 a layer at 32,768 tokens, 4 of 64, 8 held), as
``flops_afmoe`` counts them (same keys), never at the dispatch's static
bound and not at the picks that really landed. The tied head is counted
ONCE (one (T, E) x (E, V) product forward): the lookup is a gather.
``tests/test_flops_lfm2.py`` holds the hand counts.
"""

from __future__ import annotations

from benchmark.flops_afmoe import (band_pairs, expected_picks,  # noqa: F401
                                   moe_gated_experts_cost, router_width)


# ------------------------------------------------- the short convolution

def short_conv_proj_forward_flops(cfg, tokens):
    """The in-projection (E -> 3E) and the out-projection (E -> E)."""
    e = cfg["hidden_size"]
    return 2 * tokens * e * 3 * e + 2 * tokens * e * e


def short_conv_local_cost(cfg, tokens, bytes_per_el=2):
    """(FLOPs, bytes) the local part of ONE convolution mixer needs for
    ``tokens`` tokens, forward + backward, no recomputation. Forward, a
    token a channel: ``B * x`` (1), k multiplies and k - 1 adds, ``C * c``
    (1); the backward twice that. Forward reads the (tokens, 3E) product
    and writes (tokens, E); backward reads that product and the output's
    cotangent (tokens, E) and writes the product's (tokens, 3E): 11 E
    elements a token. The (E, k) taps and their gradient are left out
    (24 KB). Bound by bytes: 45 KB against 0.04 MFLOP a token at E = 2,048,
    k = 3."""
    e, k = cfg["hidden_size"], cfg["conv_L_cache"]
    flops = 3 * tokens * e * (2 * k + 1)
    bytes_ = 11 * tokens * e * bytes_per_el
    return flops, bytes_


# ------------------------------------------------------------- attention

def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def attention_layer_forward_flops(cfg, seq):
    """One sequence through one full attention layer: q, k, v in and the
    out-projection, QK^T and PV over the causal pairs."""
    e, d = cfg["hidden_size"], head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    proj = 2 * seq * e * (h + 2 * kv) * d + 2 * seq * h * d * e
    return proj + 2 * 2 * h * d * band_pairs(seq)


# ---------------------------------------------------------- feed-forward

def dense_layer_forward_flops(cfg, tokens):
    return 2 * tokens * 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def moe_layer_forward_flops(cfg, tokens):
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    router = 2 * tokens * e * router_width(cfg)
    routed = 2 * expected_picks(cfg, tokens) * 3 * e * f
    return router + routed


# ----------------------------------------------------------------- the model

def lm_forward_flops(cfg, seq):
    """One record of ``seq`` tokens through the stage and its tied head."""
    layers = 0
    for i, kind in enumerate(cfg["layer_types"]):
        layers += short_conv_proj_forward_flops(cfg, seq) \
            if kind == "conv" else attention_layer_forward_flops(cfg, seq)
        layers += dense_layer_forward_flops(cfg, seq) \
            if i < cfg["num_dense_layers"] \
            else moe_layer_forward_flops(cfg, seq)
    return layers + 2 * seq * cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_record(cfg, seq):
    return 3 * lm_forward_flops(cfg, seq)
