"""data: mean host wait for the next batch over the window, the rise of
``bigdl_train_data_wait_seconds``'s sum over the rise of its count."""
LAYER, UNIT = "data", "ms"

from benchmark import harness


def read(ctx):
    d = harness.counter_delta(ctx["before"], ctx["after"],
                              "bigdl_train_data_wait_seconds")
    if not d or not d[1]:
        return None
    return 1e3 * d[0] / d[1]
