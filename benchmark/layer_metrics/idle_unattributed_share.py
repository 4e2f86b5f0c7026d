"""training loop: share of the slice's device-idle time charged to nothing:
the host was in no program span (or the program's enqueue is not in the
trace) while the device waited. The coverage of the tracing itself."""
LAYER, UNIT = "training loop", "%"

from benchmark import timeline


def read(ctx):
    charge = timeline.charge_gaps(ctx)
    if not charge or not sum(charge.values()):
        return None
    return 100.0 * charge.get(timeline.NONE, 0.0) / sum(charge.values())
