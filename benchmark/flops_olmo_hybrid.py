"""Operations and bytes of the Olmo-Hybrid decoder LM (gated delta-rule
linear-attention and full-attention layers 3 : 1 over dense SwiGLU
feed-forwards, an untied head) from shapes, as ``flops.py`` counts the
dense decoder: what the algorithm needs, forward + backward = 3 x forward
for every matrix product, recomputation (block remat, flash's backward)
NOT counted, elementwise work left out of the model's count, a
multiply-add 2 FLOPs.

A linear layer's products are its six in-projections (hidden -> 2 H d_k +
2 H d_v + 2 H) and its out-projection (H d_v -> hidden) over the heads
HELD. Its recurrence is counted as the RECURRENCE, ``6 d_k d_v`` FLOPs a
token and head forward (``k^T S``, the rank-1 correction and write, ``S^T
q``: 2 d_k d_v each), whatever form computes it: the chunked form's
triangular systems and masked products are how, not what. It is bound by
BYTES and has a cost of its own (``delta_rule_cost``) for
``delta_rule_roofline``. Attention is counted at the causal half's
query-key pairs with the diagonal. ``tests/test_flops_olmo_hybrid.py``
holds the hand counts.
"""

from __future__ import annotations

from benchmark.flops_afmoe import band_pairs  # noqa: F401


def linear_layers(cfg):
    return list(cfg["layer_types"]).count("linear_attention")


# ------------------------------------------------------------ the delta rule

def delta_proj_forward_flops(cfg, tokens):
    """q, k, v, the output gate, beta and the decay's input in, the heads'
    outputs out."""
    e, h = cfg["hidden_size"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return 2 * tokens * e * (2 * h * dk + 2 * h * dv + 2 * h) \
        + 2 * tokens * h * dv * e


def delta_rule_forward_flops(cfg, tokens):
    """One layer's recurrences: ``6 d_k d_v`` a token and head."""
    return 6 * tokens * cfg["linear_num_value_heads"] \
        * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]


def delta_rule_cost(cfg, tokens, bytes_per_el=2):
    """(FLOPs, bytes) the recurrences of ONE linear layer need for
    ``tokens`` tokens, forward + backward (the backward twice the
    forward), no recomputation. Forward reads q, k, v (compute dtype), g
    and beta (float32) and writes o; backward reads those six and o's
    cotangent and writes the five inputs' cotangents: a head and token 3
    x (2 d_k + d_v) + 3 d_v elements and 3 x 2 floats, 3,480 B at 96 /
    192 in bf16. Bound by bytes: 52 KB against 5.0 MFLOP a token at 15
    heads (a machine balance of 240 FLOPs a byte asks for 12.5 MFLOP)."""
    h = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    flops = 3 * delta_rule_forward_flops(cfg, tokens)
    bytes_ = tokens * h * ((3 * (2 * dk + dv) + 3 * dv) * bytes_per_el
                           + 3 * 2 * 4)
    return flops, bytes_


# ------------------------------------------------------------- attention

def attention_layer_forward_flops(cfg, seq):
    """One sequence through one full attention layer: q, k, v in and the
    out-projection over the heads held, QK^T and PV over the causal
    pairs."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    proj = 2 * seq * e * (h + 2 * kv) * d + 2 * seq * h * d * e
    return proj + 2 * 2 * h * d * band_pairs(seq)


# ----------------------------------------------------------------- the model

def mlp_forward_flops(cfg, tokens):
    return 2 * tokens * 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def lm_forward_flops(cfg, seq):
    """One record of ``seq`` tokens through the stage and its head."""
    layers = 0
    for kind in cfg["layer_types"]:
        if kind == "linear_attention":
            layers += delta_proj_forward_flops(cfg, seq) \
                + delta_rule_forward_flops(cfg, seq)
        else:
            layers += attention_layer_forward_flops(cfg, seq)
        layers += mlp_forward_flops(cfg, seq)
    return layers + 2 * seq * cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_record(cfg, seq):
    return 3 * lm_forward_flops(cfg, seq)
