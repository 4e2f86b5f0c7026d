"""One run of one cell: ``python -m benchmark.run --workload <cell> --seed
<n> --seconds <s> --trace <0|1>``, from the root of a checkout.

Prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device`` and,
traced, ``breakdown``. Without a TPU, or with fewer chips than the cell
asks for, it prints no result and exits 2. ``--rehearse`` runs the same
control flow at the tiny sizes of each file's ``rehearsal`` group on
whatever backend jax has; its line says ``correct: false`` and
``rehearsal: true``, and the exit code is 3: never a measurement.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()      # set-up is counted from here

import argparse     # noqa: E402
import json         # noqa: E402
import sys          # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import harness
    manifest = harness.load_manifest()
    cell, config = harness.load_cell(args.workload, args.rehearse)
    seconds = args.seconds if args.seconds is not None \
        else float(manifest["run_seconds"])

    import bigdl_tpu  # noqa: F401 - fixes the compile cache: env var, else <checkout>/.jax_cache
    import jax
    # the cache is never trimmed: under the chip machine's 192 MiB limit a
    # cell's programs (80 MB for the LM step alone) evict each other, and
    # every run compiles again
    jax.config.update("jax_compilation_cache_max_size", -1)
    try:
        devices = harness.devices_for(cell["chips"], args.rehearse)
    except harness.BenchFailure as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2

    phases = {"import_s": time.perf_counter() - _T_START}
    ctx = {"cell": cell, "config": config, "seed": args.seed,
           "seconds": seconds, "trace": bool(args.trace),
           "devices": devices, "phases": phases, "manifest": manifest,
           "rehearse": args.rehearse}
    try:
        result = harness.load_kind(cell["kind"]).run(ctx)
    except harness.BenchFailure as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2

    setup_s = ctx["t_window_open"] - _T_START
    values = dict(result["values"], setup_s=setup_s)
    e2e = harness.cell_e2e_entries(manifest, cell["name"])
    e2e_names = {m["name"] for m in e2e}
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices),
           "memory_peak_bytes": harness.memory_peak_bytes(
               ctx.get("live_bytes", 0), ctx["counters_after"])}
    out = {"correct": bool(result["correct"]) and not args.rehearse,
           "attempted": int(result["attempted"]),
           "failed": int(result["failed"])}
    if args.trace:
        from benchmark import trace_context
        tctx = trace_context.build(ctx, result, values, dev)
        out["metrics"] = harness.layer_metric_values(
            manifest, cell["name"], e2e_names, tctx)
        dev["busy_s"], dev["window_s"] = tctx["busy_s"], tctx["window_s"]
        if tctx.get("breakdown"):
            out["breakdown"] = tctx["breakdown"]
    else:
        missing = e2e_names - set(values)
        if missing:
            print(f"benchmark: no value for {sorted(missing)}",
                  file=sys.stderr)
            return 2
        out["metrics"] = {m["name"]: {"value": float(values[m["name"]]),
                                      "unit": m["unit"]} for m in e2e}
    out["device"] = dev
    if args.rehearse:
        out["rehearsal"] = True
    detail = {"phases": phases, "checks": result.get("checks"),
              "values": values, "extra": result.get("extra")}
    print("benchmark detail: " + json.dumps(detail, default=str),
          file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
