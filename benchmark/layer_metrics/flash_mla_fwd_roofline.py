"""kernels: the latent flash-attention forward's share of its roofline: the
least time the chip could take for the ``flash_mla_fwd`` calls in the slice
(the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s, from
shapes: ``flops_mla.flash_mla_forward_cost``, QK^T at the query/key head
and PV at the value head over the causal pairs) over their summed device
time. Compute-bound at seq 8192, heads of 192 over 128 (about 2,000 FLOPs
a byte: 3.49 ms a call on the v5e). The prediction module's call runs over
the same number of positions as a main layer's and is costed alike."""
LAYER, UNIT = "kernels", "%"

from benchmark import flops_mla
from benchmark.layer_metrics.flash_mla_share import mla_ops


def read(ctx):
    cfg, cell = ctx["config"], ctx["cell"]
    fwd = mla_ops(ctx, ("flash_mla_fwd",))
    if not fwd or not ctx["peaks"] or "qk_nope_head_dim" not in cfg:
        return None
    need_f, need_b = flops_mla.flash_mla_forward_cost(
        cell["batch_size"], cfg["num_attention_heads"], cell["seq_len"],
        *flops_mla.head_sizes(cfg))
    least = max(need_f / ctx["peaks"]["bf16_flops_per_s"],
                need_b / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * len(fwd) / sum(op.dur for op in fwd)
