"""data: device idle an iteration charged to the loop's ``train.data`` span
(the batch fetch: for the device cache its gather and index programs),
mean over the cell's chips. Charged by cause: ``benchmark/timeline.py``."""
LAYER, UNIT = "data", "ms"

from benchmark import timeline


def read(ctx):
    return timeline.idle_ms_per_iteration(ctx, lambda k: k == "train.data")
