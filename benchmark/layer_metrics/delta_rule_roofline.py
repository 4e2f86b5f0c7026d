"""kernels: the gated delta rule's share of its roofline: the least time
the chip could take for one step's recurrences (every ``linear_attention``
layer; the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s,
forward + backward, no recomputation, from shapes:
``flops_olmo_hybrid.delta_rule_cost``) over the device time a step of the
layer ``delta_rule`` in the step's partition (all passes, so what block
remat runs again is in the time and not in the cost). The cost is the
RECURRENCE's: 6 d_k d_v FLOPs a token and head forward and twice that
backward against q, k, v, g, beta, o and their cotangents read and written
once, 52 KB a token a layer in bf16 at 15 heads of 96 / 192; bound by
BYTES. Reckoned from shapes and selected by scope, so it reads the same
work whatever implements it: a chunked form's triangular systems, masked
products and carried states are in the time alone."""
LAYER, UNIT = "kernels", "%"

from benchmark import flops_olmo_hybrid, step_partition


def cost(cfg, tokens):
    """(FLOPs, bytes) of one step's recurrences: ``delta_rule_cost`` a
    ``linear_attention`` layer."""
    layers = flops_olmo_hybrid.linear_layers(cfg)
    f, b = flops_olmo_hybrid.delta_rule_cost(cfg, tokens)
    return layers * f, layers * b


def read(ctx):
    cell, cfg = ctx["cell"], ctx["config"]
    if "linear_key_head_dim" not in cfg or "seq_len" not in cell \
            or not ctx["peaks"]:
        return None
    table = step_partition.rows(ctx)
    seconds = sum(sec for (layer, _), sec in (table or {}).items()
                  if layer == "delta_rule")
    if not seconds:
        return None
    need_f, need_b = cost(cfg, cell["batch_size"] * cell["seq_len"])
    least = max(need_f / ctx["peaks"]["bf16_flops_per_s"],
                need_b / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
