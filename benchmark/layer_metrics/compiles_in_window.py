"""start-up: rise of ``bigdl_compiles_total`` (every site) between the
window's opening and its close; must be 0."""
LAYER, UNIT = "start-up", "count"

from benchmark import harness


def read(ctx):
    return harness.counter_delta(ctx["before"], ctx["after"],
                                 "bigdl_compiles_total")
