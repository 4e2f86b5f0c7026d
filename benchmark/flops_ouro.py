"""Operations and bytes of the Ouro looped decoder LM (every layer a
full-attention block and a dense SwiGLU block, the whole stack run
``total_ut_steps`` times over the same weights, one untied head read by
every pass, one exit gate) from shapes, as ``flops.py`` counts the dense
decoder: what the algorithm needs, forward + backward = 3 x forward for
every matrix product, recomputation (block remat, flash's backward) NOT
counted, elementwise work, norms and the exit distribution left out of the
model's count, a multiply-add 2 FLOPs.

EVERY pass counts: a pass is the same products over the same weights on
another stream, so a step is ``P x layers`` layer applications, ``P`` heads
and ``P`` gate products. Attention is counted at the causal half's
query-key pairs with the diagonal. ``tests/test_flops_ouro.py`` holds the
hand counts.
"""

from __future__ import annotations

from benchmark.flops_afmoe import band_pairs


def layer_parameters(cfg):
    """One layer's matrices (the four (hidden,) norm weights apart)."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return e * (h + 2 * kv) * d + h * d * e + 3 * e * cfg["intermediate_size"]


def parameters(cfg):
    """Every parameter of the configuration as it is held: the layers with
    their four norms each, the embedding, the untied head, the final norm
    and the gate with its bias."""
    e = cfg["hidden_size"]
    return cfg["num_hidden_layers"] * (layer_parameters(cfg) + 4 * e) \
        + 2 * cfg["vocab_size"] * e + e + (e + 1)


def layer_forward_flops(cfg, seq):
    """One sequence through one layer application: its products over
    ``seq`` tokens, QK^T and PV over the causal pairs."""
    return 2 * seq * layer_parameters(cfg) \
        + 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * band_pairs(seq)


def lm_forward_flops(cfg, seq):
    """One record of ``seq`` tokens through all ``total_ut_steps`` passes:
    the layers, the head and the gate's one row, each once a pass."""
    e = cfg["hidden_size"]
    return cfg["total_ut_steps"] * (
        cfg["num_hidden_layers"] * layer_forward_flops(cfg, seq)
        + 2 * seq * e * cfg["vocab_size"] + 2 * seq * e)


def train_flops_per_record(cfg, seq):
    return 3 * lm_forward_flops(cfg, seq)


def lm_head_ce_cost(cfg, tokens, passes, bytes_per_el=2):
    """(FLOPs, bytes) the head's loss over ALL passes needs for one step,
    forward and backward: three (P T, D) x (D, V) products (logits, dh,
    dW), none recomputed; h read twice and dh written in the compute dtype,
    W read twice, dW written ONCE in float32 (the passes share one head:
    their sum is one array), the targets, the row weights and the rows'
    losses as 4-byte words. The least ANY implementation needs: it reads
    the same work as one call over P x T rows or as P calls."""
    rows = passes * tokens
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    flops = 6 * rows * e * v
    bytes_ = (3 * rows * e * bytes_per_el       # h twice, dh
              + 2 * v * e * bytes_per_el        # W, twice
              + v * e * 4                       # dW
              + 3 * rows * 4)                   # targets, weights, losses
    return flops, bytes_
