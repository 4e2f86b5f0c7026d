"""model step: share of the step's device time in the layer ``batchnorm`` (the
batch-normalisation modules over ``ops/batch_norm.py``), all passes. From
the step's partition (``benchmark/step_partition.py``): operations that
start inside whole runs of the step program, each in one (layer, pass) cell,
over the table's total, mean over the cell's chips. A program without the
vocabulary (every commit before PR 36) reads nothing."""
LAYER, UNIT = "model step", "%"

from benchmark import step_partition


def read(ctx):
    return step_partition.share(ctx, layers=("batchnorm",))
