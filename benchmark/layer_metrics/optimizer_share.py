"""model step: share of the step's device time in the pass ``update``, all
layers: the compute-dtype cast of the parameters and the gradient's way back
through it (``param_cast``), the clipper (``grad_clip``), a collective the
step builder writes itself (``grad_sync``) and the optimiser's update
(``optim_update``). From the step's partition
(``benchmark/step_partition.py``): operations that start inside whole runs
of the step program, each in one (layer, pass) cell, over the table's total,
mean over the cell's chips. A program without the vocabulary (every commit
before PR 36) reads nothing."""
LAYER, UNIT = "model step", "%"

from benchmark import step_partition


def read(ctx):
    return step_partition.share(ctx, passes=("update",))
