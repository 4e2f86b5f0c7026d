"""serving engine: 95th percentile of the time to the first token, from the
``serving.request`` lane of the program's spans: the ``first_token``
instant (the admission sample reached the host) minus the lane's begin
(``submit``), over the requests whose first token fell in the slice."""
LAYER, UNIT = "serving engine", "ms"

import statistics


def lanes(ctx):
    """{rid: (begin, first_token)} on ``perf_counter`` seconds."""
    begin, first = {}, {}
    for s in ctx["spans"]:
        if s["name"] != "serving.request":
            continue
        if s["ph"] == "b":
            begin[s["id"]] = s["t0"]
        elif s["ph"] == "n" and s["args"].get("phase") == "first_token":
            first[s["id"]] = s["t0"]
    return {r: (begin[r], t) for r, t in first.items() if r in begin}


def p95(values):
    if len(values) < 2:
        return None
    return statistics.quantiles(values, n=20, method="inclusive")[-1]


def read(ctx):
    waits = [1e3 * (t - b) for b, t in lanes(ctx).values()]
    return p95(waits)
