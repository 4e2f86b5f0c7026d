"""model step: share of the step's device time in the layer ``delta_proj``
(``nn.GatedDeltaNet``'s fused in-projection, six products as one, and its
out-projection), all passes. From the step's partition
(``benchmark/step_partition.py``): operations that start inside whole runs
of the step program, each in one (layer, pass) cell, over the table's
total, mean over the cell's chips. A program without the scope (every
commit before PR 45, and every family without a delta-rule mixer) reads
nothing."""
LAYER, UNIT = "model step", "%"

from benchmark import step_partition


def read(ctx):
    return step_partition.share(ctx, layers=("delta_proj",)) or None
