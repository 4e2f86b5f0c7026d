"""data: device time an iteration of every program other than the step
itself: the device cache's index programs and batch gather (and the key
split), which run between two steps; mean over the cell's chips. A cell
whose hooks run programs (validation) would count those too."""
LAYER, UNIT = "data", "ms"

from benchmark import timeline


def read(ctx):
    n = timeline.iterations(ctx)
    if ctx.get("trace") is None or ctx.get("lo") is None or not n:
        return None
    return 1e3 * timeline.other_program_seconds(
        ctx["trace"], ctx["lo"], ctx["hi"]) / n
