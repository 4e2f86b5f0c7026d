"""The step's device time by layer and pass, for the per-layer readers PR 36
added: ``ctx["trace"]`` (the reduced xplane), ``ctx["hlo"]`` (the step
program's compiled text) and the slice's bounds handed to the PROGRAM's own
reduction, ``bigdl_tpu.telemetry.step_partition.partition`` (the one reader
of ``op_name``; the vocabulary of layers is
``bigdl_tpu.telemetry.catalogue.SCOPE_SPECS``). Computed once a run and
kept on ``ctx``.

What is counted: on each chip the operations that START inside a WHOLE run
of the step program (``rx.program_runs``, as ``step_device_ms`` finds them,
less the stump a session leaves at either end: ``whole_runs``), containers (``while``, ``conditional``, ``call``) left out, each
operation's own duration, divided by the runs; then the mean over the
cell's chips. So every operation lands in one (layer, pass) cell, the
table's total is the step's busy time, and a share is a layer's SELF time
(its scope less the scopes inside it) over that total: the shares of all
layers add to 100 and none reads low by runs / steps as the union-of-
intervals readers of ``timeline.scope_of`` do.

A program without the vocabulary reads nothing: a commit before PR 36 has
no ``bigdl_tpu.telemetry.step_partition``, and a step program served from
a compile cache that such a commit filled (jax leaves metadata out of the
cache's key) has no ``optim_update`` scope in its text.
"""

from __future__ import annotations

from benchmark import reduce_xplane as rx

_KEY = "step_partition"
_MARK = "optim_update"      # every step builder enters it since PR 36


def rows(ctx):
    """{(layer, pass): seconds a step}, mean over the cell's chips, or None
    where there is no trace, no HLO or no vocabulary in the program."""
    if _KEY not in ctx:
        ctx[_KEY] = _rows(ctx)
    return ctx[_KEY]


def _rows(ctx):
    if ctx.get("trace") is None or ctx.get("lo") is None \
            or not ctx.get("hlo") or _MARK not in ctx["hlo"]:
        return None
    try:
        from bigdl_tpu.telemetry import step_partition as sp
    except ImportError:
        return None
    tables = []
    for dev in ctx["trace"].devices:
        runs = sp.whole_runs([(m[1], m[2]) for m in rx.program_runs(
            dev, ctx["lo"], ctx["hi"])])
        if runs:
            tables.append(sp.partition(
                ctx["hlo"], ((o.name, o.t0, o.t1) for o in dev.ops), runs))
    return sp.mean_rows(tables) if tables else None


def share(ctx, layers=None, passes=None):
    """Percent of the table's total in the cells whose layer is one of
    ``layers`` and whose pass is one of ``passes`` (None: any); None where
    there is no table."""
    table = rows(ctx)
    total = sum(table.values()) if table else 0.0
    if not total:
        return None
    return 100.0 * sum(sec for (layer, pas), sec in table.items()
                       if (layers is None or layer in layers)
                       and (passes is None or pas in passes)) / total
