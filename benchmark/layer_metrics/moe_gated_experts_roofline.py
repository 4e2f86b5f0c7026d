"""kernels: the held routed GATED experts' share of their roofline: the
least time the chip could take for one step's grouped products (every
expert layer; the larger of FLOPs over peak FLOP/s and bytes over peak
bytes/s, three matrices an expert, forward + backward, from shapes:
``cost``) over the device time a step of the operations under the scope
``moe_experts``. As ``moe_experts_roofline`` reads the two-matrix experts
of the pattern-string family: reckoned at the picks a BALANCED router lands
here (tokens x 8 x 16 / 128), not at those that landed. The cell's routers
are not trained (``training.router_gradient`` ``"none"``), so what lands is
the seeded routers' draw around that count all through a run, and the
reading is the kernel's within the draw; where a cell trains a held share's
routers they drift onto it and this reads low by the drift. Bound by FLOPs
at this cell's shape (512 rows an expert against 6.29M weights: 1.57 ms of
FLOPs, 1.19 ms of bytes a layer)."""
LAYER, UNIT = "kernels", "%"

from benchmark import flops_afmoe, timeline


def cost(cfg, tokens):
    """(FLOPs, bytes) of one step's routed experts:
    ``flops_afmoe.moe_gated_experts_cost`` an expert layer."""
    layers = len(cfg["layer_types"]) - cfg["num_dense_layers"]
    f, b = flops_afmoe.moe_gated_experts_cost(cfg, tokens)
    return layers * f, layers * b


def read(ctx):
    cell, cfg = ctx["cell"], ctx["config"]
    found = timeline.scope_of(ctx, "moe_experts")
    if found is None or not ctx["peaks"] or "seq_len" not in cell \
            or "layer_types" not in cfg:
        return None
    seconds, runs = found
    need_f, need_b = cost(cfg, cell["batch_size"] * cell["seq_len"])
    least = max(need_f / ctx["peaks"]["bf16_flops_per_s"],
                need_b / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * runs / seconds
