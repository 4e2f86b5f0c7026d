"""model step: share of the step's device time in the operations no scope of
the vocabulary names (``unattributed``: a path with no layer of
``catalogue.SCOPE_SPECS`` in it): the meter of the partition's completeness.
From the step's partition (``benchmark/step_partition.py``): operations that
start inside whole runs of the step program, each in one (layer, pass) cell,
over the table's total, mean over the cell's chips. A program without the
vocabulary (every commit before PR 36) reads nothing."""
LAYER, UNIT = "model step", "%"

from benchmark import step_partition


def read(ctx):
    return step_partition.share(ctx, layers=("unattributed",))
