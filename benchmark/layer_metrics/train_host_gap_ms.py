"""training loop: device idle between consecutive runs of the step program
in the traced slice, median over the gaps of every chip."""
LAYER, UNIT = "training loop", "ms"

import statistics

from benchmark import reduce_xplane as rx


def read(ctx):
    if ctx["trace"] is None or ctx["lo"] is None:
        return None
    gaps = []
    for dev in ctx["trace"].devices:
        runs = rx.program_runs(dev, ctx["lo"], ctx["hi"])
        gaps += [max(0.0, b[1] - a[2]) for a, b in zip(runs, runs[1:])]
    return 1e3 * statistics.median(gaps) if gaps else None
