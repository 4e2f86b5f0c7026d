"""Operations and bytes of the pattern-built hybrid LM (Mamba-2, mixture of
experts, attention) from shapes, as ``flops.py`` counts the dense decoder:
what the algorithm needs, forward + backward = 3 x forward for every matrix
product, recomputation (block remat, flash's backward) NOT counted,
elementwise work left out, a multiply-add 2 FLOPs.

The routed experts are counted at the picks a BALANCED router lands on the
experts HELD here: ``tokens * num_experts_per_tok * held / router width``
(3,072 a layer at 8,192 tokens, 6 of 128, 8 held), never at the static
bound of the dispatch buffer. Not at the picks that really landed in the
run: those are values on the device (``counts`` in ``MoE._held_forward``)
that no span, scope or counter carries, and a reader is given the trace,
the step's HLO and the registry's counters (``kinds/train.py``, a file
this module may not edit). At initialisation the routers of one seed
landed 15% fewer than this (94.66% of picks absent against 93.75%), the
count varies by 17% (sd) from seed to seed and drifts as the routers
train, so ``moe_experts_roofline`` and the experts' term of
``model_flops_util`` move with the draw as well as with the kernel, and
overstate where fewer picks land (PERF.md section 7).
``tests/test_flops_hybrid.py`` holds the hand counts.
"""

from __future__ import annotations


def router_width(cfg):
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def expected_picks(cfg, tokens):
    """Picks that land on this chip's experts under a balanced router."""
    return tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / router_width(cfg)


# ------------------------------------------------------------------ the scan

def ssd_scan_forward_flops(cfg, seq):
    """The chunked scan's four products for one sequence: ``C B^T`` a group
    and ``(C B^T * decay) (dt x)`` a head over the causal half of each
    (chunk, chunk) block, the chunk states ``B^T (dt x)`` and their
    read-out ``C H`` (2 x heads x head_dim x state a token each)."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, q = cfg["n_groups"], cfg["ssm_state_size"], cfg["chunk_size"]
    inside = 2 * seq * q * (g * n + h * p) / 2
    states = 2 * 2 * seq * h * p * n
    return inside + states


def ssd_scan_cost(cfg, tokens, bytes_per_el=2):
    """(FLOPs, bytes) one Mamba-2 layer's scan needs for ``tokens`` tokens,
    forward + backward: x, B, C and dt read and y written forward; x, B, C,
    dt and dy read and dx, dB, dC, d(dt) written backward (dt float32)."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    wide = (5 * h * p + 6 * g * n) * bytes_per_el + 3 * h * 4
    return 3 * ssd_scan_forward_flops(cfg, tokens), tokens * wide


# --------------------------------------------------------------- the experts

def moe_experts_cost(cfg, tokens, bytes_per_el=2):
    """(FLOPs, bytes) the HELD routed experts of one layer need for
    ``tokens`` tokens, forward + backward: two products a pick, three times
    over; both matrices of every held expert read forward and backward and
    their gradient written once in float32; a pick's row read (x), written
    (y) forward, and read (x, dy) and written (dx) backward."""
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["n_routed_experts"]
    picks = expected_picks(cfg, tokens)
    flops = 3 * 2 * picks * 2 * e * f
    bytes_ = held * 2 * e * f * (2 * bytes_per_el + 4) \
        + 5 * picks * e * bytes_per_el
    return flops, bytes_


# ----------------------------------------------------------------- the model

def mamba_layer_forward_flops(cfg, seq):
    e = cfg["hidden_size"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    d_inner = h * p
    d_in = 2 * d_inner + 2 * g * n + h
    return 2 * seq * e * d_in + 2 * seq * d_inner * e \
        + ssd_scan_forward_flops(cfg, seq)


def moe_layer_forward_flops(cfg, seq):
    e = cfg["hidden_size"]
    router = 2 * seq * e * router_width(cfg)
    shared = 2 * seq * 2 * e * cfg["moe_shared_expert_intermediate_size"] \
        * cfg.get("n_shared_experts", 1)
    routed = 2 * expected_picks(cfg, seq) * 2 * e \
        * cfg["moe_intermediate_size"]
    return router + shared + routed


def attention_layer_forward_flops(cfg, seq):
    e, d = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    proj = 2 * seq * e * (h + 2 * kv) * d + 2 * seq * h * d * e
    return proj + 2 * 2 * h * d * seq * seq / 2     # QK^T, PV: causal half


def lm_forward_flops(cfg, seq):
    per = {"M": mamba_layer_forward_flops, "E": moe_layer_forward_flops,
           "*": attention_layer_forward_flops}
    layers = sum(per[kind](cfg, seq)
                 for kind in cfg["hybrid_override_pattern"])
    return layers + 2 * seq * cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_record(cfg, seq):
    return 3 * lm_forward_flops(cfg, seq)
