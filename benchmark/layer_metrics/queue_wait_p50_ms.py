"""serving engine: median of the ``serving.queue_wait`` events (submit ->
admission) recorded in the traced slice."""
LAYER, UNIT = "serving engine", "ms"

import statistics


def read(ctx):
    d = [s["dur"] for s in ctx["spans"]
         if s["name"] == "serving.queue_wait" and s["ph"] == "X"]
    return 1e3 * statistics.median(d) if d else None
