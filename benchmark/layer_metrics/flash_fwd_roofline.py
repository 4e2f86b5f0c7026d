"""kernels: the flash-attention forward's share of its roofline: the least
time the chip could take for the calls in the slice (the larger of FLOPs
over peak FLOP/s and bytes over peak bytes/s, from shapes:
``flops.flash_forward_cost``) over their summed device time. Compute-bound
at these shapes (seq 2048, head 64: ~512 FLOPs a byte)."""
LAYER, UNIT = "kernels", "%"

from benchmark import flops, harness
from benchmark.layer_metrics.flash_attention_share import flash_ops


def read(ctx):
    ops = flash_ops(ctx)
    fwd = [op for op, is_fwd in ops or [] if is_fwd]
    if not fwd or not ctx["peaks"]:
        return None
    builder = harness.load_builder(ctx["config"]["family"])
    need_f, need_b = flops.flash_forward_cost(
        *builder.flash_shape(ctx["config"], ctx["cell"]))
    least = max(need_f / ctx["peaks"]["bf16_flops_per_s"],
                need_b / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * len(fwd) / sum(op.dur for op in fwd)
