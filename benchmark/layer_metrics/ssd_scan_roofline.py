"""kernels: the Mamba-2 scan's share of its roofline: the least time the
chip could take for one step's scans (every ``M`` block; the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s, forward + backward,
from shapes: ``cost``) over the device time a step of the operations under
the scope ``ssd_scan``. Recomputation under block remat is in the time and
not in the cost. Bound by BYTES at this cell's shape (x, B, C, dt, y and
their gradients: 54 KB a token a layer against 8.3 MFLOP)."""
LAYER, UNIT = "kernels", "%"

from benchmark import flops_hybrid, timeline


def cost(cfg, tokens):
    """(FLOPs, bytes) of one step's scans: ``flops_hybrid.ssd_scan_cost``
    a Mamba-2 block."""
    blocks = cfg["hybrid_override_pattern"].count("M")
    f, b = flops_hybrid.ssd_scan_cost(cfg, tokens)
    return blocks * f, blocks * b


def read(ctx):
    cell, cfg = ctx["cell"], ctx["config"]
    found = timeline.scope_of(ctx, "ssd_scan")
    if found is None or not ctx["peaks"] or "seq_len" not in cell:
        return None
    seconds, runs = found
    need_f, need_b = cost(cfg, cell["batch_size"] * cell["seq_len"])
    least = max(need_f / ctx["peaks"]["bf16_flops_per_s"],
                need_b / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * runs / seconds
