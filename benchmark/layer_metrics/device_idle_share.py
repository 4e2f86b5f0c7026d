"""device: share of the traced steady slice in which no operation ran on the
device, mean over the cell's chips (1 - union of op intervals / slice)."""
LAYER, UNIT = "device", "%"


def read(ctx):
    if not ctx["window_s"] or ctx["trace"] is None or ctx["lo"] is None:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
