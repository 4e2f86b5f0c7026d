"""The step's device time, by the layer and the pass that spent it.

Under jit the whole step is one XLA program and a module's wall time means
nothing; what is left of the module is the ``jax.named_scope`` its
operations were written under, which the compiled HLO keeps as each
instruction's ``op_name`` (``jit(step)/transpose(jvp(Sequential))/
HybridDecoder/checkpoint/rematted_computation/HybridBlock/Mamba2/
mamba_local/mul``). This module is the ONE reader of that string:

- ``classify(op_name) -> (layer, pass)``: ``layer`` is the innermost
  component of the path that the vocabulary (``catalogue.SCOPE_SPECS``)
  knows, ``unattributed`` where it knows none; ``pass`` is ``update`` for
  the step's own stages after the gradient, ``recompute`` under a
  ``rematted_computation``, ``backward`` under a ``transpose(``, else
  ``forward``.
- ``partition(hlo_text, op_events, runs)``: a profile's operation events
  (named by HLO instruction) summed into ``{(layer, pass): seconds a
  step}``. Every counted event lands in exactly one cell, so the table's
  total is the step's busy time and a layer's row is its SELF time (its
  scope less the scopes inside it).

A fused instruction carries ONE instruction's metadata, so a fusion that
spans two layers is charged to one of them (``mixed_fusions`` lists them).
``Optimizer.set_profiling`` writes the table beside its profile
(``step_partition.json``, ``profile_table``); the benchmark's
``*_share`` readers of the step's layers call the same ``partition``.
No jax at import: ``read_profile`` imports ``jax.profiler`` when called.
"""

from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bigdl_tpu.telemetry.catalogue import SCOPE_SPECS

__all__ = ["UNATTRIBUTED", "PASSES", "classify", "instructions",
           "partition", "instruction_seconds", "mixed_fusions",
           "whole_runs", "mean_rows", "read_profile", "profile_table",
           "format_table", "write_report"]

UNATTRIBUTED = "unattributed"
PASSES = ("forward", "recompute", "backward", "update")
CONTAINERS = ("while", "conditional", "call")   # their bodies' ops are events
COPIES = ("copy", "copy-start", "copy-done")    # layout changes, by opcode

_SCOPES = {s.name: s for s in SCOPE_SPECS}
_CLASSES = {c: s.name for s in SCOPE_SPECS for c in s.classes}
_KERNELS = tuple((k, s.name) for s in SCOPE_SPECS for k in s.kernels)
_PART = re.compile(r"[^/()]+")
_BACKWARD = "transpose("
_RECOMPUTE = "rematted_computation"
_UNKNOWN = ("", "")                     # an instruction the text lacks


def _weak(part: str) -> Optional[str]:
    """The layer a module's class name or a Mosaic kernel's name stands
    for: weaker than an entered scope, which keeps what runs inside it."""
    layer = _CLASSES.get(part)
    if layer is None:
        layer = next((v for k, v in _KERNELS if part.startswith(k)), None)
    return layer


@functools.lru_cache(maxsize=1 << 16)    # a program repeats its paths
def classify(op_name: str) -> Tuple[str, str]:
    """(layer, pass) of an HLO instruction's ``op_name``. Where XLA joined
    several source operations (``a;b``) the first names the instruction."""
    path = op_name.split(";", 1)[0]
    layer, held = UNATTRIBUTED, False
    for part in _PART.findall(path):
        spec = _SCOPES.get(part)
        if spec is not None:            # an entered scope
            layer, held = part, not spec.group
        elif not held:
            layer = _weak(part) or layer
    if layer != UNATTRIBUTED and _SCOPES[layer].update:
        return layer, "update"
    if _RECOMPUTE in path:
        return layer, "recompute"
    return layer, "backward" if _BACKWARD in path else "forward"


# ------------------------------------------------------------------ HLO text

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s(.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r"metadata=\{[^}]*op_name=\"([^\"]*)\"")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_EVENT = re.compile(r"^%?([^\s=]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")


@functools.lru_cache(maxsize=2)     # a chip a call, the same text
def instructions(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """{instruction name: (opcode, op_name)} of a compiled HLO text. An
    instruction the compiler made itself carries no path (on the TPU:
    copies and slices prefetched into fast memory as ``*-start`` /
    ``*-done`` pairs, bitcast fusions, with no metadata at all; a layout
    copy of an argument, named ``params[...]`` after it; XLA's own
    expansions, named ``reduce_sum``): it takes the ``op_name`` of the
    first instruction that USES its result, through other such
    instructions, so a prefetch belongs to the layer it feeds; with no
    such user, that of its first operand that has a path; else it keeps
    what it had."""
    table, operands = {}, {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        code = _OPCODE.search(" " + m.group(2))
        name = _OP_NAME.search(m.group(2))
        table[m.group(1)] = (code.group(1) if code else "",
                             name.group(1) if name else "")
        operands[m.group(1)] = _OPERAND.findall(m.group(2))
    bare = [n for n, (_, op) in table.items() if "/" not in op]
    if not bare:
        return table
    users = {}
    for name, ops in operands.items():
        for op in ops:
            if op in table:
                users.setdefault(op, []).append(name)
    for around in (users, operands):
        for _ in range(8):              # chains: start -> done -> bitcast
            left = []
            for name in bare:
                found = next((table[o][1] for o in around.get(name, ())
                              if o in table and "/" in table[o][1]), "")
                if found:
                    table[name] = (table[name][0], found)
                else:
                    left.append(name)
            if len(left) == len(bare):
                break
            bare = left
    return table


def mixed_fusions(hlo_text: str) -> Dict[str, Tuple[str, ...]]:
    """{fusion instruction: the layers of the instructions fused into it},
    for the fusions that hold more than one layer: each is charged whole to
    the one its own ``op_name`` names."""
    bodies, current = {}, None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = bodies.setdefault(head.group(1), set())
            continue
        if current is None:
            continue
        if line.strip() == "}":
            current = None
            continue
        name = _OP_NAME.search(line)
        if name:
            current.add(classify(name.group(1))[0])
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m or " fusion(" not in line:
            continue
        calls = _CALLS.search(line)
        layers = bodies.get(calls.group(1), ()) if calls else ()
        layers = tuple(sorted(set(layers) - {UNATTRIBUTED}))
        if len(layers) > 1:
            out[m.group(1)] = layers
    return out


# ------------------------------------------------------------------ events

def _seconds(table, op_events, runs) -> Dict[str, float]:
    """{instruction name: seconds a step} over the events that count: not a
    container's, and started inside a WHOLE run of the step program (an
    event of a run the profile cut off would be divided by a run it is not
    part of). ``runs`` None: every event counts, divided by 1 (a profile
    without program events)."""
    starts = [r[0] for r in runs] if runs is not None else None
    out = {}
    for event, t0, t1 in op_events:
        name = _EVENT.match(event).group(1)
        if table.get(name, _UNKNOWN)[0] in CONTAINERS:
            continue
        if starts is not None:
            i = bisect.bisect_right(starts, t0) - 1
            if i < 0 or t0 >= runs[i][1]:
                continue
        out[name] = out.get(name, 0.0) + (t1 - t0)
    n = len(runs) if runs else 1
    return {k: v / n for k, v in out.items()}


def _rows(table, seconds) -> Dict[Tuple[str, str], float]:
    rows = {}
    for name, sec in seconds.items():
        cell = classify(table.get(name, _UNKNOWN)[1])
        rows[cell] = rows.get(cell, 0.0) + sec
    return rows


def instruction_seconds(hlo_text, op_events, runs) -> Dict[str, float]:
    """{instruction name: seconds a step} of one device (see
    ``partition``)."""
    return _seconds(instructions(hlo_text), op_events, runs)


def partition(hlo_text: str, op_events: Iterable[Tuple[str, float, float]],
              runs: Optional[Sequence[Tuple[float, float]]]
              ) -> Dict[Tuple[str, str], float]:
    """{(layer, pass): seconds a step} of ONE device: ``op_events`` its
    operation events ``(name, t0, t1)`` (the HLO instruction's name, or
    the whole instruction text a TPU trace names an event by), ``runs`` the
    ``(t0, t1)`` of the step program's runs that lie wholly inside the
    profile, sorted. An event whose instruction the text does not hold is
    ``unattributed``."""
    table = instructions(hlo_text)
    return _rows(table, _seconds(table, op_events, runs))


def whole_runs(runs: Sequence[Tuple[float, float]]
               ) -> List[Tuple[float, float]]:
    """``runs`` (sorted ``(t0, t1)`` of the step program on one device)
    without the stumps at a profile's ends: a session opens and closes
    while a step runs, and the trace then holds that run from its first
    recorded operation, or to its last, as a program event like the
    others. A first or last run under nine tenths of the median run is
    such a stump."""
    runs = list(runs)
    if len(runs) >= 3:
        durs = sorted(t1 - t0 for t0, t1 in runs)
        floor = 0.9 * durs[len(durs) // 2]
        if runs[-1][1] - runs[-1][0] < floor:
            runs.pop()
        if runs[0][1] - runs[0][0] < floor:
            runs.pop(0)
    return runs


def mean_rows(tables: Sequence[Dict]) -> Dict:
    """The mean of several devices' tables, cell by cell."""
    out = {}
    for t in tables:
        for k, v in t.items():
            out[k] = out.get(k, 0.0) + v / len(tables)
    return out


# ----------------------------------------------------------------- a profile

def _events(line):
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def read_profile(path: str, program: str, table: Dict):
    """[(op_events, runs)] a device of an ``*.xplane.pb``: a TPU profile's
    ``/device:TPU:n`` planes (``XLA Ops`` events; ``runs`` the ``XLA
    Modules`` events of ``program``, the HLO module's name, that lie
    between the plane's first and last operation, ``whole_runs`` of them). A
    profile without a device plane (a CPU run) gives ONE pseudo-device: the
    host threads' events named by an instruction of ``table``, and ``runs``
    None."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices = []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: line for line in plane.lines}
        ops = _events(lines["XLA Ops"]) if "XLA Ops" in lines else []
        if not ops or "XLA Modules" not in lines:
            continue
        lo, hi = min(o[1] for o in ops), max(o[2] for o in ops)
        runs = whole_runs(sorted((t0, t1) for name, t0, t1 in _events(
            lines["XLA Modules"]) if name.split("(")[0] == program
            and t0 >= lo and t1 <= hi))
        if runs:
            devices.append((ops, runs))
    if devices:
        return devices
    ops = [e for plane in data.planes if plane.name == "/host:CPU"
           for line in plane.lines for e in _events(line) if e[0] in table]
    return [(ops, None)] if ops else []


def _host_runs(ops) -> int:
    """How often a profile without program events ran the step: the least
    number of times one of its instructions ran."""
    counts = {}
    for name, _, _ in ops:
        counts[name] = counts.get(name, 0) + 1
    return max(1, min(counts.values()))


def profile_table(log_dir: str, hlo_text: str) -> Optional[dict]:
    """The newest profile under ``log_dir`` reduced to the step's
    partition: ``{"program", "devices", "runs", "step_ms", "rows":
    [{"layer", "pass", "ms", "share"}], "layers", "passes", "copies",
    "unattributed_top", "mixed_fusions_top"}`` (ms a step, mean over the
    devices; shares in % of ``step_ms``; ``copies`` is the part of each
    layer's time in ``copy`` instructions, the compiler's layout changes;
    the two lists hold the five largest instructions no scope names and
    the five largest fusions that span layers). None where the profile holds no event of the program.
    Without program events (a CPU run) the divisor is a count
    (``_host_runs``) and other programs' instructions of the same name
    count too: a rehearsal, not a measurement."""
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    head = re.match(r"HloModule\s+([\w.\-]+)", hlo_text)
    if not found or not head:
        return None
    table = instructions(hlo_text)
    devices = read_profile(found[-1], head.group(1), table)
    if not devices:
        return None
    seconds, n_runs = [], 0
    for ops, runs in devices:
        n = len(runs) if runs is not None else _host_runs(ops)
        scale = 1.0 if runs is not None else 1.0 / n
        seconds.append({k: v * scale
                        for k, v in _seconds(table, ops, runs).items()})
        n_runs += n
    instr = mean_rows(seconds)
    rows = _rows(table, instr)
    total = sum(rows.values())
    if not total:
        return None

    def ms(x):
        return round(1e3 * x, 4)

    layers, passes, copies = {}, {p: 0.0 for p in PASSES}, {}
    for (layer, pas), sec in rows.items():
        layers[layer] = layers.get(layer, 0.0) + sec
        passes[pas] += sec
    for name, sec in instr.items():
        code, op = table.get(name, _UNKNOWN)
        if code in COPIES:
            layer = classify(op)[0]
            copies[layer] = copies.get(layer, 0.0) + sec

    def largest(names):
        return sorted(((instr[n], n) for n in names if n in instr),
                      reverse=True)[:5]

    mixed = mixed_fusions(hlo_text)
    return {
        "program": head.group(1), "devices": len(devices),
        "runs": n_runs / len(devices), "step_ms": ms(total),
        "rows": [{"layer": layer, "pass": pas, "ms": ms(sec),
                  "share": round(100.0 * sec / total, 3)}
                 for (layer, pas), sec in sorted(
                     rows.items(), key=lambda kv: -kv[1])],
        "layers": {k: ms(v) for k, v in sorted(
            layers.items(), key=lambda kv: -kv[1])},
        "passes": {k: ms(v) for k, v in passes.items()},
        "copies": {k: ms(v) for k, v in sorted(
            copies.items(), key=lambda kv: -kv[1])},
        "unattributed_top": [
            {"instruction": n, "ms": ms(sec),
             "opcode": table.get(n, _UNKNOWN)[0],
             "op_name": table.get(n, _UNKNOWN)[1]}
            for sec, n in largest(
                n for n in instr if classify(
                    table.get(n, _UNKNOWN)[1])[0] == UNATTRIBUTED)],
        "mixed_fusions_top": [
            {"instruction": n, "ms": ms(sec), "layers": list(mixed[n]),
             "charged_to": classify(table[n][1])[0]}
            for sec, n in largest(mixed)]}


def format_table(report: dict) -> str:
    """The report's rows as text: layer x pass, ms a step and % of it."""
    lines = [f"step partition of {report['program']}: "
             f"{report['step_ms']:.3f} ms a step over "
             f"{report['runs']:.0f} runs on {report['devices']} device(s)",
             f"{'layer':16s} {'pass':10s} {'ms':>10s} {'%':>7s}"]
    for row in report["rows"]:
        lines.append(f"{row['layer']:16s} {row['pass']:10s} "
                     f"{row['ms']:10.3f} {row['share']:7.2f}")
    return "\n".join(lines)


def write_report(log_dir: str, hlo_text: str) -> Optional[dict]:
    """``profile_table`` written to ``<log_dir>/step_partition.json``."""
    report = profile_table(log_dir, hlo_text)
    if report is not None:
        with open(os.path.join(log_dir, "step_partition.json"), "w",
                  encoding="utf-8") as f:
            json.dump(report, f, indent=1)
    return report
