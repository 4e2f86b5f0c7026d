"""model step: share of the step's device time in the layer ``loop_exit``
(a looped decoder's exit gate: the gate's product with every pass's stream,
the log-sigmoids, the exit distribution over the passes, its entropy and
the loss's last sums), all passes of the step and every pass of the loop.
From the step's partition (``benchmark/step_partition.py``): operations
that start inside whole runs of the step program, each in one (layer, pass)
cell, over the table's total, mean over the cell's chips. A program without
the scope (every commit before PR 48, and every family without an exit
gate) reads nothing."""
LAYER, UNIT = "model step", "%"

from benchmark import step_partition


def read(ctx):
    return step_partition.share(ctx, layers=("loop_exit",)) or None
