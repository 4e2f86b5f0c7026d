"""kernels: share of the device's busy time in the mixture-of-experts
blocks' own work: the operations under the scopes ``moe_route`` (router,
top-k, the sort of the local picks), ``moe_experts`` (the grouped product
over the held experts' rows) and ``moe_shared`` (the shared expert), each
a union of intervals inside runs of the step program (the scopes hold
disjoint operations, so their times add)."""
LAYER, UNIT = "kernels", "%"

from benchmark import timeline

SCOPES = ("moe_route", "moe_experts", "moe_shared")


def read(ctx):
    found = [timeline.scope_of(ctx, scope) for scope in SCOPES]
    if not any(found) or not ctx["busy_s"]:
        return None
    return 100.0 * sum(f[0] for f in found if f) / ctx["busy_s"]
