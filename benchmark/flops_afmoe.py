"""Operations and bytes of the ``afmoe`` decoder LM (sliding-window and full
attention layers with a gated output, dense and expert SwiGLU
feed-forwards) from shapes, as ``flops.py`` counts the dense decoder: what
the algorithm needs, forward + backward = 3 x forward for every matrix
product, recomputation (block remat, flash's backward) NOT counted,
elementwise work left out, a multiply-add 2 FLOPs.

Attention is counted at the query-key PAIRS each layer's band holds
(``band_pairs``): a full layer the causal half and its diagonal, a
``sliding_attention`` layer ``sliding_window`` keys a query once the window
is full. The routed experts are counted at the picks a BALANCED router
lands on the experts HELD here, ``tokens * num_experts_per_tok * held /
router width`` (8,192 a layer at 8,192 tokens, 8 of 128, 16 held), never at
the dispatch's static bound and not at the picks that really landed
(``flops_hybrid``'s docstring says why a reader cannot see those).
``tests/test_flops_afmoe.py`` holds the hand counts.
"""

from __future__ import annotations


def router_width(cfg):
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def expected_picks(cfg, tokens):
    """Picks that land on this chip's experts under a balanced router."""
    return tokens * cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / router_width(cfg)


# ------------------------------------------------------------- attention

def band_pairs(seq, window=None):
    """Query-key pairs of one head over one sequence under a causal mask
    whose query i sees the keys ``(i - window, i]``: ``sum_i min(i + 1,
    window)``; without a window (or one at least as long as the sequence)
    the causal half with its diagonal."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def flash_band_forward_cost(batch, heads, seq, head_dim, window,
                            bytes_per_el=2):
    """(FLOPs, bytes) the banded causal flash-attention FORWARD needs for
    one call: QK^T and PV over the band's pairs; q, k, v read once, o
    written once, the fp32 log-sum-exp row written once."""
    flops = 2 * 2 * batch * heads * head_dim * band_pairs(seq, window)
    bytes_ = 4 * batch * heads * seq * head_dim * bytes_per_el \
        + 4 * batch * heads * seq
    return flops, bytes_


def attention_layer_forward_flops(cfg, seq, kind):
    e, d = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    # q, k, v and the output gate in; the out-projection
    proj = 2 * seq * e * (2 * h + 2 * kv) * d + 2 * seq * h * d * e
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    return proj + 2 * 2 * h * d * band_pairs(seq, window)


# ---------------------------------------------------------- feed-forward

def moe_gated_experts_cost(cfg, tokens, bytes_per_el=2):
    """(FLOPs, bytes) the HELD routed experts of one layer need for
    ``tokens`` tokens, forward + backward: three products a pick (gate, up,
    down), three times over; the three matrices of every held expert read
    forward and backward and their gradient written once in float32; a
    pick's row read (x), written (y) forward, and read (x, dy) and written
    (dx) backward."""
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["num_experts"]
    picks = expected_picks(cfg, tokens)
    flops = 3 * 3 * picks * 2 * e * f
    bytes_ = held * 3 * e * f * (2 * bytes_per_el + 4) \
        + 5 * picks * e * bytes_per_el
    return flops, bytes_


def dense_layer_forward_flops(cfg, seq):
    return 2 * seq * 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def moe_layer_forward_flops(cfg, seq):
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    router = 2 * seq * e * router_width(cfg)
    shared = 2 * seq * 3 * e * f * cfg.get("num_shared_experts", 1)
    routed = 2 * expected_picks(cfg, seq) * 3 * e * f
    return router + shared + routed


# ----------------------------------------------------------------- the model

def lm_forward_flops(cfg, seq):
    layers = 0
    for i, kind in enumerate(cfg["layer_types"]):
        layers += attention_layer_forward_flops(cfg, seq, kind)
        layers += dense_layer_forward_flops(cfg, seq) \
            if i < cfg["num_dense_layers"] \
            else moe_layer_forward_flops(cfg, seq)
    return layers + 2 * seq * cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_record(cfg, seq):
    return 3 * lm_forward_flops(cfg, seq)
