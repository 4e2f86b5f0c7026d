"""The serving kind: one in-process ``ContinuousLMServer`` over the bf16
twin, driven open-loop through its public ``submit()`` from threads of this
process (the process that holds the chip is the only one that touches jax).

``submit()`` blocks until the whole answer is there, so each request waits
in a thread of its own (blocked on an event: no CPU) and what can be seen
from outside is its completion time. A request's latency runs from the time
it was DUE, not from when it was sent, so a stall charges the requests
behind it; how late the generator itself ran is reported.
"""

from __future__ import annotations

import json
import sys
import threading
import time

import numpy as np

from benchmark import harness, traffic


CONTROL_MIN_FAIL_SHARE = 0.5     # of the served tokens, under each control


class _Client:
    """One request's journey, written by its own thread."""

    __slots__ = ("due", "sent", "done", "tokens", "error", "thread")

    def __init__(self, due):
        self.due, self.sent, self.done = due, None, None
        self.tokens, self.error, self.thread = 0, None, None


def _serve_one(server, req, rec, timeout):
    rec.sent = time.perf_counter()
    try:
        out = server.submit(req["prompt"], req["max_new"], timeout=timeout)
        rec.tokens = len(out)
    except Exception as e:  # noqa: BLE001 - the client boundary: a failed
        rec.error = f"{type(e).__name__}: {e}"      # request is a result
    rec.done = time.perf_counter()


def offer(server, requests, t0, until, timeout, on_tick=None):
    """Send each request at ``t0 + due`` from this thread until ``until``
    (perf_counter); returns the records of those sent."""
    recs = []
    for req in requests:
        due = t0 + req["due"]
        if due >= until:
            break
        while True:
            now = time.perf_counter()
            if on_tick is not None:
                on_tick(now)
            if now >= due:
                break
            time.sleep(min(due - now, 0.01))
        rec = _Client(due)
        rec.thread = threading.Thread(
            target=_serve_one, args=(server, req, rec, timeout), daemon=True)
        rec.thread.start()
        recs.append(rec)
    while on_tick is not None and time.perf_counter() < until:
        on_tick(time.perf_counter())
        time.sleep(0.005)
    return recs


def _serve_probes(server, probes, timeout):
    outs = [None] * len(probes)

    def one(i):
        outs[i] = server.submit(probes[i]["prompt"], probes[i]["max_new"],
                                timeout=timeout)

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(probes))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return outs


def reference_check(builder, server, params, cfg, cell, seed):
    """Seeded prompts served together before the window. Every token the
    server emitted must be, in the plain reference's full forward over
    prompt + emitted, within ``logit_gap_tol`` of that position's largest
    logit (logits, not token equality: near-ties flip on bf16 rounding).

    The check has to be able to fail. The same served tokens are therefore
    judged against each broken reference of ``builder.reference_controls``
    (attention removed, the q/k bias removed, another rope base), and under
    each at least ``CONTROL_MIN_FAIL_SHARE`` of them must be OUTSIDE the
    tolerance. A control that passes means the tokens do not depend on that
    piece of the mathematics, and the run is not ``correct``."""
    spec = cell["reference"]
    tol = spec["logit_gap_tol"]
    probes = traffic.probe_prompts(seed, cfg["vocab_size"], spec["prompts"],
                                   spec["prompt_len"], spec["new_tokens"])
    outs = _serve_probes(server, probes, spec.get("timeout_s", 900))
    if any(out is None or len(out) != probe["max_new"]
           for probe, out in zip(probes, outs)):
        return {"ok": False, "why": "a probe request came back short"}
    logits_of = builder.reference_logits_fn(cfg)

    def gaps(ref_params, theta):
        """max - logit[emitted] at every emitted position."""
        found = []
        for probe, out in zip(probes, outs):
            ids = probe["prompt"] + [int(t) for t in out]
            rows = np.asarray(logits_of(ref_params, ids[:-1], theta))[
                len(probe["prompt"]) - 1:]
            toks = np.asarray(out, np.int64) - 1
            found.append(rows.max(-1) - rows[np.arange(len(toks)), toks])
        return np.concatenate(found)

    true = gaps(params, None)
    under = {name: gaps(*control) for name, control in
             builder.reference_controls(cfg, params).items()}
    shares = {name: float(np.mean(g > tol)) for name, g in under.items()}
    ok = bool(np.isfinite(true).all() and true.max() <= tol
              and all(v >= CONTROL_MIN_FAIL_SHARE for v in shares.values()))

    def summary(g):
        return {"max": float(g.max()), "mean": float(g.mean()),
                "p50": float(np.median(g)),
                "p90": float(np.percentile(g, 90))}

    return {"ok": ok, "worst_logit_gap": float(true.max()),
            "tokens_checked": int(true.size),
            "tokens_not_the_argmax": int((true > 0).sum()),
            "gaps": summary(true), "control_fail_share": shares,
            "control_gaps": {k: summary(g) for k, g in under.items()}}


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) \
        else None


def measure(server, requests, seconds, lead_s, timeout, saturated,
            devices=(), trace=None):
    """One phase of traffic: starts now, window = [lead, lead + seconds]."""
    t0 = time.perf_counter()
    t_open, t_close = t0 + lead_s, t0 + lead_s + seconds
    state = {"before": None, "mid": None, "live": []}

    def tick(now):
        if state["before"] is None and now >= t_open:
            state["before"] = harness.counters()
            state["live"].append(harness.live_bytes(devices))
        if state["mid"] is None and now >= (t_open + t_close) / 2:
            state["mid"] = server.queue_depth
            state["live"].append(harness.live_bytes(devices))
        if (trace is not None and not trace.started
                and now >= trace.starts_at(t_close, seconds)):
            trace.start()

    recs = offer(server, requests, t0, t_close, timeout, tick)
    after = harness.counters()
    depth_end = server.queue_depth
    state["live"].append(harness.live_bytes(devices))
    if trace is not None and trace.started:
        trace.stop()
    due_in = [r for r in recs if t_open <= r.due < t_close]
    if not saturated:               # every request due in the window ends
        for r in due_in:
            r.thread.join(timeout=timeout + 5)
    done_in = [r for r in recs if r.done is not None and r.error is None
               and t_open < r.done <= t_close]
    failed_in = [r for r in recs if r.done is not None and r.error is not None
                 and t_open < r.done <= t_close]
    sent_in = [r for r in recs if r.sent is not None
               and t_open <= r.sent < t_close]
    lags = [1e3 * (r.sent - r.due) for r in sent_in]
    out = {"t_open": t_open, "t_close": t_close, "recs": recs,
           "before": state["before"] or after, "after": after,
           "live_bytes": max(state["live"]),
           "due_in_window": len(due_in),
           "offered_per_s": len(due_in) / seconds,
           "due_completed_in_window": sum(
               1 for r in due_in if r.done is not None and r.error is None
               and r.done <= t_close),
           "queue_depth_mid": state["mid"], "queue_depth_end": depth_end,
           "completed_in_window": len(done_in),
           "tokens_per_s": sum(r.tokens for r in done_in) / seconds,
           # the knee's test: completions keep pace with arrivals (as many
           # complete in the window as 97% of those due in it) and the queue
           # is no deeper at its end than at its middle
           "keeps_pace": bool(len(done_in) >= 0.97 * len(due_in)
                              and depth_end <= max(state["mid"] or 0, 2)),
           "generator_lag_p95_ms": _pct(lags, 95),
           "generator_lag_max_ms": max(lags) if lags else None}
    if saturated:
        lat = [1e3 * (r.done - r.due) for r in done_in]
        out.update(attempted=len(done_in) + len(failed_in),
                   failed=len(failed_in),
                   sat_latency_p95_ms=_pct(lat, 95))
    else:
        ok = [r for r in due_in if r.done is not None and r.error is None]
        bad = len(due_in) - len(ok)
        lat = [1e3 * (r.done - r.due) for r in ok]
        norm = [l / max(r.tokens, 1) for l, r in zip(lat, ok)]
        if bad:                     # a failure counts as the worst
            worst = max(lat + [1e3 * timeout])
            lat += [worst] * bad
            norm += [worst] * bad
        out.update(attempted=len(due_in), failed=bad,
                   latency_p95_ms=_pct(lat, 95), latency_p50_ms=_pct(lat, 50),
                   norm_latency_p50_ms=_pct(norm, 50))
    return out


def start_server(cell, cfg, seed, phases):
    """Build the model from the seed and start the cell's server over its
    bf16 twin. Returns (server, builder, a copy of the twin's weights under
    the reference's names: the server's donating programs consume the
    twin's own buffers)."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.models.serving import ContinuousLMServer

    builder = harness.load_builder(cfg["family"])
    srv = cell["server"]
    t = time.perf_counter()
    twin = builder.serve_model(builder.build(cfg, seed), cfg, seed)
    phases["build_s"] = time.perf_counter() - t
    params = jax.tree_util.tree_map(jnp.copy, builder.reference_params(twin))
    server = ContinuousLMServer(
        twin, slots=srv["slots"], max_len=srv["max_len"],
        decode_block=srv["decode_block"], prefill_chunk=srv["prefill_chunk"],
        max_new_tokens=cell["traffic_params"]["output"]["max"], greedy=True,
        eos_id=None, seed=seed)
    return server, builder, params


def run(ctx):
    cell, cfg, seed = ctx["cell"], ctx["config"], ctx["seed"]
    phases, seconds = ctx["phases"], ctx["seconds"]
    compiles0 = harness.counters()
    server, builder, params = start_server(cell, cfg, seed, phases)
    try:
        t = time.perf_counter()
        ref = reference_check(builder, server, params, cfg, cell, seed)
        del params
        print("benchmark reference check: " + json.dumps(ref),
              file=sys.stderr, flush=True)     # kept if the run is cut later
        phases["warmup_and_reference_s"] = time.perf_counter() - t

        saturated = bool(cell.get("saturated"))
        timeout = cell.get("request_timeout_s", 120)
        lead = cell["lead_seconds"]
        trace = harness.TracedSlice(cell["name"]) if ctx["trace"] else None
        requests = traffic.serve_requests(
            seed, cell["traffic_params"], cfg["vocab_size"],
            lead + seconds + 1.0)
        m = measure(server, requests, seconds, lead, timeout, saturated,
                    ctx["devices"], trace)
    finally:
        server.close()
    for r in m["recs"]:                 # closed: every waiter returns now
        r.thread.join(timeout=30)
    alive = sum(r.thread.is_alive() for r in m["recs"])

    ctx["t_window_open"] = m["t_open"]
    by_site = {k: m["after"][k] - compiles0.get(k, 0.0) for k in m["after"]
               if k.startswith("bigdl_compiles_total")}
    in_window = harness.counter_delta(m["before"], m["after"],
                                      "bigdl_compiles_total") or 0.0
    checks = {"reference": ref, "compiles_by_site": by_site,
              "compiles_in_window": in_window, "threads_left": alive,
              "dead_reason": server.dead_reason}
    correct = bool(ref["ok"] and in_window == 0 and m["failed"] == 0
                   and alive == 0 and server.dead_reason is None
                   and m["attempted"] > 0)
    if saturated:
        values = {"serve_tokens_per_s": m["tokens_per_s"]}
    else:
        values = {"serve_latency_p95_ms": m["latency_p95_ms"],
                  "serve_norm_latency_p50_ms": m["norm_latency_p50_ms"]}
    extra = {k: v for k, v in m.items()
             if k not in ("recs", "before", "after")}
    extra["slots"] = cell["server"]["slots"]
    ctx.update(counters_before=m["before"], counters_after=m["after"],
               live_bytes=m["live_bytes"], device_trace=trace,
               span_events=trace.events if trace is not None else [],
               span_names=("serving.prefill", "serving.insert",
                           "serving.decode_block"))
    extra["slice_wall_s"] = trace.wall_s if trace is not None else None
    return {"correct": correct, "attempted": m["attempted"],
            "failed": m["failed"], "checks": checks, "values": values,
            "extra": extra}
