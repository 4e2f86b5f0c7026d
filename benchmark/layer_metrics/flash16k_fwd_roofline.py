"""kernels: the FULL flash-attention forward's share of its roofline at the
cell's length (16,384 keys of head 128 in the cell that lists it): the
least time the chip could take for the ``flash_fwd`` calls in the slice
(the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s at the
causal half's query-key pairs, from shapes:
``flops_smallthinker.flash_forward_cost``) over their summed device time.
The calls are found by NAME (``flash_fwd``: a call told of no band), so the
banded calls of the same operand shape are not among them, which
``flash_fwd_roofline`` (calls by operand shape) cannot say. Compute-bound
at 16,384 x 128 (about 2,000 FLOPs a byte)."""
LAYER, UNIT = "kernels", "%"

from benchmark import flops_smallthinker, harness
from benchmark.layer_metrics.flash_band_share import band_ops


def forward_roofline(ctx, kernel, window_key=None):
    """Percent of their least time that the forward calls named ``kernel``
    took, at the pairs a window of ``cfg[window_key]`` leaves (None: the
    causal half); None where there is nothing to read."""
    cfg = ctx["config"]
    builder = harness.load_builder(cfg["family"])
    if not ctx["peaks"] or not hasattr(builder, "flash_shape") \
            or (window_key and not cfg.get(window_key)):
        return None
    fwd = band_ops(ctx, (kernel,))
    if not fwd:
        return None
    need_f, need_b = flops_smallthinker.flash_forward_cost(
        *builder.flash_shape(cfg, ctx["cell"]),
        cfg[window_key] if window_key else None)
    least = max(need_f / ctx["peaks"]["bf16_flops_per_s"],
                need_b / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * len(fwd) / sum(op.dur for op in fwd)


def read(ctx):
    return forward_roofline(ctx, "flash_fwd")
