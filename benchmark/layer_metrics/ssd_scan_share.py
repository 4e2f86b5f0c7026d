"""kernels: share of the device's busy time in the operations under the
scope ``ssd_scan`` (the chunked Mamba-2 scan of every ``M`` block, forward,
its recomputation under block remat, and backward), as a union of
intervals inside runs of the step program."""
LAYER, UNIT = "kernels", "%"

from benchmark import timeline


def read(ctx):
    found = timeline.scope_of(ctx, "ssd_scan")
    if found is None or not ctx["busy_s"]:
        return None
    return 100.0 * found[0] / ctx["busy_s"]
