"""Operations and bytes of the SmallThinker decoder LM (full and
sliding-window GQA layers, ReGLU experts routed from the layer's input)
from shapes, as ``flops.py`` counts the dense decoder: what the algorithm
needs, forward + backward = 3 x forward for every matrix product,
recomputation (block remat, flash's backward) NOT counted, elementwise work
left out, a multiply-add 2 FLOPs.

Attention is counted at the query-key PAIRS each layer's band holds
(``band_pairs``): a layer whose ``sliding_window_layout`` is 0 the causal
half and its diagonal (134.2M a head at 16,384 tokens), one whose entry is
1 ``sliding_window_size`` keys a query once the window is full (58.7M at
4,096 over 16,384). The routed experts are counted at the picks a BALANCED
router lands on the experts HELD here, ``tokens *
moe_num_active_primary_experts * held / router width`` (12,288 a layer at
16,384 tokens, 6 of 64, 8 held), never at the dispatch's static bound and
not at the picks that really landed (``flops_hybrid``'s docstring says why
a reader cannot see those). ``tests/test_flops_smallthinker.py`` holds the
hand counts.
"""

from __future__ import annotations


def router_width(cfg):
    return cfg.get("published", {}).get("moe_num_primary_experts",
                                        cfg["moe_num_primary_experts"])


def expected_picks(cfg, tokens):
    """Picks that land on this chip's experts under a balanced router."""
    return tokens * cfg["moe_num_active_primary_experts"] \
        * cfg["moe_num_primary_experts"] / router_width(cfg)


# ------------------------------------------------------------- attention

def band_pairs(seq, window=None):
    """Query-key pairs of one head over one sequence under a causal mask
    whose query i sees the keys ``(i - window, i]``: ``sum_i min(i + 1,
    window)``; without a window (or one at least as long as the sequence)
    the causal half with its diagonal."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def flash_forward_cost(batch, heads, seq, head_dim, window=None,
                       bytes_per_el=2):
    """(FLOPs, bytes) the causal flash-attention FORWARD needs for one
    call, banded or full: QK^T and PV over the band's pairs; q, k, v read
    once, o written once, the fp32 log-sum-exp row written once. (K and V
    are counted at the QUERY heads, as the kernel is handed them: grouped
    heads are expanded before the call.)"""
    flops = 2 * 2 * batch * heads * head_dim * band_pairs(seq, window)
    bytes_ = 4 * batch * heads * seq * head_dim * bytes_per_el \
        + 4 * batch * heads * seq
    return flops, bytes_


def layer_window(cfg, i):
    return cfg["sliding_window_size"] if cfg["sliding_window_layout"][i] \
        else None


def attention_layer_forward_flops(cfg, seq, i):
    e, d = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    # q, k, v in; the out-projection
    proj = 2 * seq * e * (h + 2 * kv) * d + 2 * seq * h * d * e
    return proj + 2 * 2 * h * d * band_pairs(seq, layer_window(cfg, i))


# ---------------------------------------------------------------- experts

def moe_reglu_experts_cost(cfg, tokens, bytes_per_el=2):
    """(FLOPs, bytes) the HELD routed experts of one layer need for
    ``tokens`` tokens, forward + backward: three products a pick (gate, up,
    down), three times over; the three matrices of every held expert read
    forward and backward and their gradient written once in float32; a
    pick's row read (x), written (y) forward, and read (x, dy) and written
    (dx) backward."""
    e, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    held = cfg["moe_num_primary_experts"]
    picks = expected_picks(cfg, tokens)
    flops = 3 * 3 * picks * 2 * e * f
    bytes_ = held * 3 * e * f * (2 * bytes_per_el + 4) \
        + 5 * picks * e * bytes_per_el
    return flops, bytes_


def moe_layer_forward_flops(cfg, seq):
    e, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    router = 2 * seq * e * router_width(cfg)
    routed = 2 * expected_picks(cfg, seq) * 3 * e * f
    return router + routed


# ----------------------------------------------------------------- the model

def lm_forward_flops(cfg, seq):
    layers = sum(attention_layer_forward_flops(cfg, seq, i)
                 + moe_layer_forward_flops(cfg, seq)
                 for i in range(cfg["num_hidden_layers"]))
    return layers + 2 * seq * cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_record(cfg, seq):
    return 3 * lm_forward_flops(cfg, seq)
