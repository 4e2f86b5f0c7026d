"""collectives: payload bytes of the all-reduces in the compiled step's HLO
(``benchmark/hlo_collectives.py``), per step and chip."""
LAYER, UNIT = "collectives", "bytes"


def read(ctx):
    ar = ctx["collectives"].get("all-reduce")
    return float(ar["payload_bytes"]) if ar else None
