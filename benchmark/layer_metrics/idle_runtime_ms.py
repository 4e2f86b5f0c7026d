"""device: device idle an iteration that no host code can remove: before a
program that was already enqueued when the previous one ended, and between
two operations of one running program, mean over the cell's chips. Charged
by cause: ``benchmark/timeline.py``."""
LAYER, UNIT = "device", "ms"

from benchmark import timeline


def read(ctx):
    return timeline.idle_ms_per_iteration(ctx,
                                          lambda k: k == timeline.RUNTIME)
