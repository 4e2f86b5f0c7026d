"""Operations and bytes of the ``joyai_llm_flash`` decoder LM (latent
attention, a dense and then expert SwiGLU feed-forwards, one
multi-token-prediction module) from shapes, as ``flops.py`` counts the
dense decoder: what the algorithm needs, forward + backward = 3 x forward
for every matrix product, recomputation (block remat, flash's backward) NOT
counted, elementwise work left out, a multiply-add 2 FLOPs.

Latent attention is counted EXPANDED, as training runs it: the two down-
and the two up-projections, ``QK^T`` at the whole query/key head
(``qk_nope_head_dim + qk_rope_head_dim``) and ``PV`` at ``v_head_dim`` over
the causal pairs with their diagonal, the out-projection. The routed
experts are counted at the picks a BALANCED router lands on the experts
HELD here (``flops_afmoe``'s docstring). The prediction module is its
(2E -> E) projection, one expert layer and a second pass through the head,
over the ``seq - 1`` positions that have a next token.
``tests/test_flops_mla.py`` holds the hand counts.
"""

from __future__ import annotations

# the causal pairs with their diagonal and the dense SwiGLU layer are the
# same counts as the afmoe family's (same keys)
from benchmark.flops_afmoe import (band_pairs,  # noqa: F401
                                   dense_layer_forward_flops)


def router_width(cfg):
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def expected_picks(cfg, tokens):
    """Picks that land on this chip's experts under a balanced router."""
    return tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / router_width(cfg)


# ------------------------------------------------------------- attention

def head_sizes(cfg):
    """(the query/key head, the value head)."""
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def flash_mla_forward_cost(batch, heads, seq, qk_head, v_head,
                           bytes_per_el=2):
    """(FLOPs, bytes) the causal flash-attention FORWARD needs for one
    call whose query/key head and value head differ: QK^T at ``qk_head``
    and PV at ``v_head`` over the causal pairs; q and k read once at
    ``qk_head``, v read and o written once at ``v_head``, the fp32
    log-sum-exp row written once."""
    flops = 2 * batch * heads * (qk_head + v_head) * band_pairs(seq)
    bytes_ = 2 * batch * heads * seq * (qk_head + v_head) * bytes_per_el \
        + 4 * batch * heads * seq
    return flops, bytes_


def latent_projection_params(cfg):
    """Weights of one latent-attention layer's five projections."""
    e, n = cfg["hidden_size"], cfg["num_attention_heads"]
    qk, dv = head_sizes(cfg)
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (e * rq + rq * n * qk + e * (rkv + cfg["qk_rope_head_dim"])
            + rkv * n * (cfg["qk_nope_head_dim"] + dv) + n * dv * e)


def attention_layer_forward_flops(cfg, seq):
    qk, dv = head_sizes(cfg)
    return 2 * seq * latent_projection_params(cfg) \
        + flash_mla_forward_cost(1, cfg["num_attention_heads"], seq, qk,
                                 dv)[0]


# ---------------------------------------------------------- feed-forward

def moe_layer_forward_flops(cfg, seq):
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    router = 2 * seq * e * router_width(cfg)
    shared = 2 * seq * 3 * e * f * cfg.get("n_shared_experts", 1)
    routed = 2 * expected_picks(cfg, seq) * 3 * e * f
    return router + shared + routed


# ----------------------------------------------------------------- the model

def head_forward_flops(cfg, seq):
    return 2 * seq * cfg["hidden_size"] * cfg["vocab_size"]


def mtp_forward_flops(cfg, seq):
    """The prediction modules (0 or 1) over the positions that have a next
    token."""
    if not cfg.get("num_nextn_predict_layers", 0):
        return 0
    t, e = seq - 1, cfg["hidden_size"]
    return (2 * t * 2 * e * e + attention_layer_forward_flops(cfg, t)
            + moe_layer_forward_flops(cfg, t) + head_forward_flops(cfg, t))


def lm_forward_flops(cfg, seq):
    dense = min(cfg.get("first_k_dense_replace", 0),
                cfg["num_hidden_layers"])
    layers = cfg["num_hidden_layers"] * attention_layer_forward_flops(
        cfg, seq)
    layers += dense * dense_layer_forward_flops(cfg, seq)
    layers += (cfg["num_hidden_layers"] - dense) \
        * moe_layer_forward_flops(cfg, seq)
    return layers + head_forward_flops(cfg, seq) \
        + mtp_forward_flops(cfg, seq)


def train_flops_per_record(cfg, seq):
    return 3 * lm_forward_flops(cfg, seq)
