"""kernels: the fused LM-head cross-entropy's share of its roofline where
ONE weighted pass takes the rows of every pass of a looped decoder
(``fused_lm_head_ce(row_weight=)``): the least time the chip could take
for one step's head over all ``total_ut_steps`` passes (the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s, from shapes:
``flops_ouro.lm_head_ce_cost``) over the device time a step of the layer
``lm_head_ce`` in the step's partition. Compute-bound at 4 x 4,096 rows,
D=2,048, V=49,152 (about 8,000 FLOPs a byte). ``lm_head_ce_roofline``
counts one pass a step and does not list such a cell. Reckoned from shapes
and selected by scope, so it reads the same work as one call or as one a
pass; a configuration that does not loop reads nothing."""
LAYER, UNIT = "kernels", "%"

from benchmark import flops_ouro, step_partition


def read(ctx):
    cell, cfg = ctx["cell"], ctx["config"]
    if "total_ut_steps" not in cfg or "seq_len" not in cell \
            or not ctx["peaks"]:
        return None
    table = step_partition.rows(ctx)
    seconds = sum(sec for (layer, _), sec in (table or {}).items()
                  if layer == "lm_head_ce")
    if not seconds:
        return None
    need_f, need_b = flops_ouro.lm_head_ce_cost(
        cfg, cell["batch_size"] * cell["seq_len"], cfg["total_ut_steps"])
    least = max(need_f / ctx["peaks"]["bf16_flops_per_s"],
                need_b / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
