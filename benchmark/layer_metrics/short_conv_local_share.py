"""model step: share of the step's device time in the layer
``short_conv_local`` (``nn.ShortConv`` but its projections: the split of
the in-projection's output, the two gates and the causal depthwise
convolution between them), all passes. From the step's partition
(``benchmark/step_partition.py``): operations that start inside whole runs
of the step program, each in one (layer, pass) cell, over the table's
total, mean over the cell's chips. A program without the scope (every
commit before PR 41, and every family without a convolution mixer) reads
nothing."""
LAYER, UNIT = "model step", "%"

from benchmark import step_partition


def read(ctx):
    return step_partition.share(ctx, layers=("short_conv_local",)) or None
