"""kernels: the banded flash-attention forward's share of its roofline: the
least time the chip could take for the ``flash_band_fwd`` calls in the
slice (the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s at
the query-key pairs the BAND holds, from shapes:
``flops_afmoe.flash_band_forward_cost``) over their summed device time.
Every tile such a call meets is masked and the tiles on the band's two
edges are partly empty, so at equal kernel quality it reads below
``flash_fwd_roofline``. Compute-bound at seq 8192, window 2048, head 128
(about 900 FLOPs a byte)."""
LAYER, UNIT = "kernels", "%"

from benchmark import flops_afmoe, harness
from benchmark.layer_metrics.flash_band_share import band_ops


def read(ctx):
    cfg = ctx["config"]
    fwd = band_ops(ctx, ("flash_band_fwd",))
    if not fwd or not ctx["peaks"] or not cfg.get("sliding_window"):
        return None
    builder = harness.load_builder(cfg["family"])
    need_f, need_b = flops_afmoe.flash_band_forward_cost(
        *builder.flash_shape(cfg, ctx["cell"]), cfg["sliding_window"])
    least = max(need_f / ctx["peaks"]["bf16_flops_per_s"],
                need_b / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * len(fwd) / sum(op.dur for op in fwd)
