"""collectives: all-reduce instructions in the compiled step's HLO."""
LAYER, UNIT = "collectives", "count"


def read(ctx):
    ar = ctx["collectives"].get("all-reduce")
    return float(ar["count"]) if ar else None
