"""collectives: per step, the time an all-reduce (or any collective) is in
flight on a chip while no other operation runs on it, mean over the chips;
steps are the runs of the step program inside the traced slice."""
LAYER, UNIT = "collectives", "ms"

from benchmark import reduce_xplane as rx


def read(ctx):
    if ctx["trace"] is None or ctx["lo"] is None:
        return None
    per_dev = []
    for dev in ctx["trace"].devices:
        runs = rx.program_runs(dev, ctx["lo"], ctx["hi"])
        if not runs:
            continue
        exposed, in_flight = rx.exposed_collective_seconds(
            dev, runs[0][1], runs[-1][2])
        if in_flight == 0.0:
            return None         # no collective found under a known name
        per_dev.append(exposed / len(runs))
    return 1e3 * sum(per_dev) / len(per_dev) if per_dev else None
