"""training loop: device idle an iteration charged to the loop's own spans:
``train.dispatch``, ``train.sync``, ``train.log``, ``train.hooks`` (and
``train.validate`` inside it), ``train.epoch_end``, and what of
``train.iteration`` none of its children covers (triggers, the end test),
mean over the cell's chips. Charged by cause: ``benchmark/timeline.py``."""
LAYER, UNIT = "training loop", "ms"

from benchmark import timeline


def read(ctx):
    return timeline.idle_ms_per_iteration(
        ctx, lambda k: k.startswith("train.") and k != "train.data")
