"""kernels: share of the device's busy time in the operations under the
scope ``mla_proj`` (``nn.LatentAttention`` but its attention core: the two
down-projections, the latents' norms, the two up-projections, the
rotation and the assembly of q and k, the out-projection, forward and
backward, the prediction module's layer among them), as a union of
intervals inside runs of the step program, mean over the cell's chips. A
program without the scope (every commit before PR 34) reads nothing."""
LAYER, UNIT = "kernels", "%"

from benchmark import timeline


def read(ctx):
    found = timeline.scope_of(ctx, "mla_proj")
    if found is None or not ctx["busy_s"]:
        return None
    return 100.0 * found[0] / ctx["busy_s"]
