"""Negative controls of a train cell's ``correct`` gate: the faults that the
cell's builder can plant in the SYSTEM (``builder.FAULTS``, ``builder.
planted``), each run through the comparison that decides ``correct``
(``kinds.train.reference_check``) at the cell's own limits.

    python -m benchmark.controls --workload <cell> --seed <n> [--rehearse]

The plain reference is computed once, on the sound model; each control then
compares the faulty system with those numbers. One JSON line a control, on
standard output: ``ok`` false means the gate sees the fault. The sound
system comes first and must read ``ok`` true.
"""

from __future__ import annotations

import argparse
import json
import sys

from benchmark import harness
from benchmark.kinds import train as kind


class _Reckoned:
    """The builder, with the reference's two numbers as already computed."""

    def __init__(self, builder, sound):
        self.reference_batch = builder.reference_batch
        self._numbers = (sound["reference_loss"],
                         sound["reference_grad_norm"])

    def reference_loss_and_grad_norm(self, model, cfg, data, labels):
        return self._numbers


def run(cell, cfg, seed, faults=None):
    """[(name, ``reference_check``'s result)], the sound system first."""
    builder = harness.load_builder(cfg["family"])
    model = builder.build(cfg, seed)
    criterion = builder.criterion(cfg)
    policy = kind._policy(cell["precision"])
    sound = kind.reference_check(builder, model, criterion, policy, cfg,
                                 cell, seed, {})
    out = [("sound", sound)]
    for fault in (builder.FAULTS if faults is None else faults):
        with builder.planted(model, fault):
            out.append((fault, kind.reference_check(
                _Reckoned(builder, sound), model, criterion, policy, cfg,
                cell, seed, {})))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell, cfg = harness.load_cell(args.workload, rehearse=args.rehearse)
    import bigdl_tpu  # noqa: F401 - fixes the compile cache, as run.py
    import jax
    jax.config.update("jax_compilation_cache_max_size", -1)
    try:
        harness.devices_for(cell["chips"], args.rehearse)
    except harness.BenchFailure as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    tol = cell["reference"]
    for name, res in run(cell, cfg, args.seed):
        rel = {"loss_rel": abs(res["system_loss"] - res["reference_loss"])
               / abs(res["reference_loss"]),
               "grad_norm_rel": abs(res["system_grad_norm"]
                                    - res["reference_grad_norm"])
               / abs(res["reference_grad_norm"])}
        print(json.dumps({"control": name, "ok": res["ok"], **rel,
                          "loss_rtol": tol["loss_rtol"],
                          "grad_norm_rtol": tol["grad_norm_rtol"], **res}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
