"""Find a serve cell's knee, once, when the cell is defined: not part of
the contract's command, and no run of it is a measurement of a PR.

    python -m benchmark.sweep_knee --workload <cell> --rates 2,3,4,5 \
        --seeds 1,2,3 --seconds 30

One server per seed; at each rate one phase of the cell's traffic
(``kinds/serve.measure``: lead, then a window of ``--seconds``), the backlog
drained between phases. Prints one JSON line per phase with the load that
was really OFFERED in the window (a seed's Poisson count is not the nominal
rate), what completed, the queue at the window's middle and end, and
``keeps_pace``. The knee is the highest offered load at which every seed
keeps pace; the cell files then carry 0.8 x and 2-3 x it as numbers.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import harness, traffic
    from benchmark.kinds import serve
    cell, cfg = harness.load_cell(args.workload, args.rehearse)
    import bigdl_tpu  # noqa: F401 - fixes the compile cache
    try:
        devices = harness.devices_for(cell["chips"], args.rehearse)
    except harness.BenchFailure as e:
        print(f"sweep_knee: {e}", file=sys.stderr)
        return 2
    timeout = cell.get("request_timeout_s", 120)
    lead = cell["lead_seconds"]
    for seed in (int(s) for s in args.seeds.split(",")):
        server, _, _ = serve.start_server(cell, cfg, seed, {})
        try:
            for rate in (float(r) for r in args.rates.split(",")):
                spec = dict(cell["traffic_params"], rate_per_s=rate)
                requests = traffic.serve_requests(
                    seed, spec, cfg["vocab_size"], lead + args.seconds + 1.0)
                m = serve.measure(server, requests, args.seconds, lead,
                                  timeout, bool(cell.get("saturated")),
                                  devices)
                for r in m.pop("recs"):     # drain before the next rate
                    r.thread.join(timeout=timeout)
                del m["before"], m["after"]
                print(json.dumps(dict(m, seed=seed, rate_per_s=rate)),
                      flush=True)
        finally:
            server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
