"""serving engine: 95th percentile of the gap between consecutive tokens of
one request as they reach the host: the first at the lane's ``first_token``
instant, the others at the end of the ``serving.decode_block`` that emitted
them (its ``rids`` and ``tokens`` arguments), so the tokens of one block
arrive together and most gaps are 0 at ``decode_block`` > 1."""
LAYER, UNIT = "serving engine", "ms"

from benchmark.layer_metrics.ttft_p95_ms import lanes, p95


def token_times(ctx):
    """{rid: [time of each token on the host, ...]}."""
    times = {r: [t] for r, (_, t) in lanes(ctx).items()}
    blocks = sorted((s for s in ctx["spans"]
                     if s["name"] == "serving.decode_block"
                     and s["ph"] == "X" and "tokens" in s["args"]),
                    key=lambda s: s["t0"])
    for s in blocks:
        end = s["t0"] + s["dur"]
        for rid, n in zip(s["args"]["rids"], s["args"]["tokens"]):
            if rid in times:
                times[rid] += [end] * n
    return times


def read(ctx):
    gaps = [1e3 * (b - a) for ts in token_times(ctx).values()
            for a, b in zip(ts, ts[1:])]
    return p95(gaps)
