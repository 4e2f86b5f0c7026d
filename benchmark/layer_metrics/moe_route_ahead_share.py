"""kernels: share of the step's device time in the layer ``moe_route_ahead``
(``parallel/expert.py``, a held expert layer whose router reads the stream
from AHEAD of its attention, ``MoE(router_input="given")``: the router's
product, top-k, the softmax over the picked logits, the sort and count of
the local picks and their gathers), all passes. From the step's partition
(``benchmark/step_partition.py``): operations that start inside whole runs
of the step program, each in one (layer, pass) cell, over the table's
total, mean over the cell's chips. A program without the scope (every
commit before PR 38, and every family whose routers read their own block's
input) reads nothing."""
LAYER, UNIT = "kernels", "%"

from benchmark import step_partition


def read(ctx):
    return step_partition.share(ctx, layers=("moe_route_ahead",)) or None
