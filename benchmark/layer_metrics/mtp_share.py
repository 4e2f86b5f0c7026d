"""model step: share of the device's busy time in the operations under the
scope ``mtp`` (the multi-token-prediction module: its two norms and
projection, its latent-attention and expert blocks, its norm and its pass
through the shared head and the loss, forward and backward), as a union
of intervals inside runs of the step program, mean over the cell's chips.
It overlaps the kernels' shares: the module's flash calls, its
``mla_proj``, its experts and its ``lm_head_ce`` count here AND there. A
program without the scope (every commit before PR 34) reads nothing."""
LAYER, UNIT = "model step", "%"

from benchmark import timeline


def read(ctx):
    found = timeline.scope_of(ctx, "mtp")
    if found is None or not ctx["busy_s"]:
        return None
    return 100.0 * found[0] / ctx["busy_s"]
