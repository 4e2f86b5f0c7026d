"""serving engine: median duration of the ``serving.decode_block`` spans in
the traced slice (one dispatch of ``decode_block`` tokens for every slot,
host sync included)."""
LAYER, UNIT = "serving engine", "ms"

import statistics


def read(ctx):
    d = [s["dur"] for s in ctx["spans"]
         if s["name"] == "serving.decode_block" and s["ph"] == "X"]
    return 1e3 * statistics.median(d) if d else None
