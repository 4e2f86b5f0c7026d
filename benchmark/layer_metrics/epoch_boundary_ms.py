"""training loop: the whole device-idle gap between the two runs of the step
program on either side of a ``train.epoch_end`` span, mean over the
boundaries in the slice and the cell's chips. A view of its own, not a term
of the idle sum (its time is already in ``idle_in_loop_ms`` and
``idle_in_data_ms``)."""
LAYER, UNIT = "training loop", "ms"

import statistics

from benchmark import reduce_xplane as rx, timeline


def read(ctx):
    host = timeline.host_of(ctx)
    if host is None:
        return None
    gaps = timeline.epoch_boundaries(ctx["trace"], host, ctx["lo"],
                                     ctx["hi"],
                                     rx.device_clock_lag(ctx["trace"]))
    return 1e3 * statistics.mean(gaps) if gaps else None
