"""model step: device time of one run of the step program (its ``XLA
Modules`` event), median over the runs wholly inside the traced slice and
over the cell's chips."""
LAYER, UNIT = "model step", "ms"

import statistics

from benchmark import reduce_xplane as rx


def read(ctx):
    if ctx["trace"] is None or ctx["lo"] is None:
        return None
    durs = [m[2] - m[1] for dev in ctx["trace"].devices
            for m in rx.program_runs(dev, ctx["lo"], ctx["hi"])]
    return 1e3 * statistics.median(durs) if durs else None
