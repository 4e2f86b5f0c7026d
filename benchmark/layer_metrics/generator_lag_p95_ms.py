"""load generator: 95th percentile of (sent - due) over the requests sent
inside the window: a starved generator is not a fast server."""
LAYER, UNIT = "load generator", "ms"


def read(ctx):
    return ctx["extra"].get("generator_lag_p95_ms")
