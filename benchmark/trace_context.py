"""What the per-layer readers are handed after a traced run: the reduced
device trace of the steady slice, the program's spans and counter deltas on
the benchmark's clock, the run's own numbers, the peaks. Fixed file."""

from __future__ import annotations

from benchmark import harness, reduce_xplane as rx


def build(ctx, result, values, dev):
    cell, cfg = ctx["cell"], ctx["config"]
    tctx = {"cell": cell, "config": cfg, "values": values,
            "extra": result.get("extra") or {},
            "before": ctx.get("counters_before") or {},
            "after": ctx.get("counters_after") or {},
            "spans": ctx.get("span_events") or [],
            "hlo": ctx.get("hlo", ""),
            "collectives": ctx.get("collectives") or {},
            "steps_in_window": ctx.get("steps_in_window"),
            "step_seconds": ctx.get("step_seconds"),
            "memory_peak_bytes": dev["memory_peak_bytes"],
            "chips": len(ctx["devices"]),
            "peaks": None, "trace": None, "lo": None, "hi": None,
            "busy_s": 0.0, "window_s": 0.0, "to_perf": None}
    if not ctx["rehearse"]:
        tctx["peaks"] = harness.load_peaks(dev["kind"])
    dt = ctx.get("device_trace")
    path = dt.xplane_path() if dt is not None else None
    if path is None:
        return tctx
    trace = rx.load(path)
    tctx["trace"] = trace
    bounds = rx.slice_bounds(trace)
    if bounds is None:                  # no device plane (a CPU rehearsal)
        tctx["window_s"] = (dt.t_stop or 0.0) - (dt.t_start or 0.0)
        return tctx
    lo, hi = bounds
    tctx["lo"], tctx["hi"] = lo, hi
    tctx["busy_s"], tctx["window_s"] = rx.busy_and_window(trace, lo, hi)
    to_perf = rx.to_perf_counter(trace, dt.t_anchor)
    tctx["to_perf"] = to_perf
    gaps = rx.idle_gaps(trace.devices[0], lo, hi)[:200]    # first device's
    tctx["breakdown"] = {
        "device_ops": rx.top_ops(trace, lo, hi),
        "idle_gaps": rx.attribute_gaps(
            gaps, tctx["spans"], to_perf,
            ctx.get("span_names", ()))}
    return tctx
