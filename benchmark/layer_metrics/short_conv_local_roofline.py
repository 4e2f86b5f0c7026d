"""kernels: the convolution mixers' local part's share of its roofline: the
least time the chip could take for one step's local parts (every ``conv``
layer; the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s,
forward + backward, no recomputation, from shapes:
``flops_lfm2.short_conv_local_cost``) over the device time a step of the
layer ``short_conv_local`` in the step's partition (all passes, so what
block remat runs again is in the time and not in the cost). Bound by BYTES:
forward reads the (tokens, 3E) product and writes (tokens, E); backward
reads that product and the output's cotangent and writes the product's;
45 KB a token a layer in bf16 against 0.04 MFLOP. Reckoned from shapes and
selected by scope, so it reads the same work whatever implements it."""
LAYER, UNIT = "kernels", "%"

from benchmark import flops_lfm2, step_partition


def cost(cfg, tokens):
    """(FLOPs, bytes) of one step's local parts: ``short_conv_local_cost``
    a ``conv`` layer."""
    layers = list(cfg["layer_types"]).count("conv")
    f, b = flops_lfm2.short_conv_local_cost(cfg, tokens)
    return layers * f, layers * b


def read(ctx):
    cell, cfg = ctx["cell"], ctx["config"]
    if "conv_L_cache" not in cfg or "seq_len" not in cell \
            or not ctx["peaks"]:
        return None
    table = step_partition.rows(ctx)
    seconds = sum(sec for (layer, _), sec in (table or {}).items()
                  if layer == "short_conv_local")
    if not seconds:
        return None
    need_f, need_b = cost(cfg, cell["batch_size"] * cell["seq_len"])
    least = max(need_f / ctx["peaks"]["bf16_flops_per_s"],
                need_b / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
