"""kernels: the held routed ReGLU experts' share of their roofline: the
least time the chip could take for one step's grouped products (every
layer; the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s,
three matrices an expert, forward + backward, from shapes: ``cost``) over
the device time a step of the operations under the scope ``moe_experts``.
As ``moe_gated_experts_roofline`` reads the SwiGLU experts of the ``afmoe``
family (whose keys this family lacks): reckoned at the picks a BALANCED
router lands here (tokens x 6 x 8 / 64), not at those that landed. The
cell's routers are not trained (``training.router_gradient`` ``"none"``),
so what lands is the seeded routers' draw around that count all through a
run. Bound by FLOPs at the cell's shape (1,536 rows an expert against
5.90M weights: 2.21 ms of FLOPs, 0.85 ms of bytes a layer)."""
LAYER, UNIT = "kernels", "%"

from benchmark import flops_smallthinker, timeline


def cost(cfg, tokens):
    """(FLOPs, bytes) of one step's routed experts:
    ``flops_smallthinker.moe_reglu_experts_cost`` a layer."""
    f, b = flops_smallthinker.moe_reglu_experts_cost(cfg, tokens)
    return cfg["num_hidden_layers"] * f, cfg["num_hidden_layers"] * b


def read(ctx):
    cell, cfg = ctx["cell"], ctx["config"]
    if "moe_ffn_hidden_size" not in cfg or "seq_len" not in cell \
            or not ctx["peaks"]:
        return None
    found = timeline.scope_of(ctx, "moe_experts")
    if found is None:
        return None
    seconds, runs = found
    need_f, need_b = cost(cfg, cell["batch_size"] * cell["seq_len"])
    least = max(need_f / ctx["peaks"]["bf16_flops_per_s"],
                need_b / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * runs / seconds
