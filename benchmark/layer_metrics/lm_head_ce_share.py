"""kernels: share of the device's busy time in the operations under the
scope ``lm_head_ce`` (the fused LM-head cross-entropy: two ``while`` loops a
step and what surrounds them), as a union of intervals inside runs of the
step program, mean over the cell's chips."""
LAYER, UNIT = "kernels", "%"

from benchmark import timeline


def read(ctx):
    found = timeline.scope_of(ctx, "lm_head_ce")
    if found is None or not ctx["busy_s"]:
        return None
    return 100.0 * found[0] / ctx["busy_s"]
