"""serving engine: 95th percentile of (completion - due) over the requests
completed inside the saturated cell's window. Recorded, never judged: the
queue grows all run, so it swings with the smallest change."""
LAYER, UNIT = "serving engine", "ms"


def read(ctx):
    return ctx["extra"].get("sat_latency_p95_ms")
