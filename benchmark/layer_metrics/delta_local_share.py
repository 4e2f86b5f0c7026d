"""model step: share of the step's device time in the layer
``delta_local`` (``nn.GatedDeltaNet`` but its projections and its
recurrence: the causal convolution and SiLU on q, k and v, their two L2
norms, beta and the log-decay, the gated RMSNorm of the recurrence's
output), all passes. From the step's partition
(``benchmark/step_partition.py``): operations that start inside whole runs
of the step program, each in one (layer, pass) cell, over the table's
total, mean over the cell's chips. A program without the scope (every
commit before PR 45, and every family without a delta-rule mixer) reads
nothing."""
LAYER, UNIT = "model step", "%"

from benchmark import step_partition


def read(ctx):
    return step_partition.share(ctx, layers=("delta_local",)) or None
