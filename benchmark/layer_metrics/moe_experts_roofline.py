"""kernels: the held routed experts' share of their roofline: the least
time the chip could take for one step's grouped products (every ``E``
block; the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s,
forward + backward, from shapes: ``cost``) over the device time a step of
the operations under the scope ``moe_experts``. The cost is reckoned at the
picks a BALANCED router lands here (tokens x 6 x 8 / 128), not at the
dispatch's static bound, so a form that multiplied the padding would read
a sixteenth. It is not reckoned at the picks that really landed: ``ctx``
holds the trace, the step's HLO and the registry's counters, and the landed
counts are device values that none of them carries (``flops_hybrid``'s
docstring), so the share also moves with the routers' draw: 17% (sd) over
seeds, too high where fewer picks land than a balanced router's. FLOPs and bytes nearly tie at this cell's shape (8 experts x
9.98M weights read twice and their float32 gradient written, against 384
rows an expert: 0.93 ms of FLOPs, 0.88 ms of bytes a block)."""
LAYER, UNIT = "kernels", "%"

from benchmark import flops_hybrid, timeline


def cost(cfg, tokens):
    """(FLOPs, bytes) of one step's routed experts:
    ``flops_hybrid.moe_experts_cost`` an expert block."""
    blocks = cfg["hybrid_override_pattern"].count("E")
    f, b = flops_hybrid.moe_experts_cost(cfg, tokens)
    return blocks * f, blocks * b


def read(ctx):
    cell, cfg = ctx["cell"], ctx["config"]
    found = timeline.scope_of(ctx, "moe_experts")
    if found is None or not ctx["peaks"] or "seq_len" not in cell:
        return None
    seconds, runs = found
    need_f, need_b = cost(cfg, cell["batch_size"] * cell["seq_len"])
    least = max(need_f / ctx["peaks"]["bf16_flops_per_s"],
                need_b / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * runs / seconds
