"""One timeline: the program's spans and the runtime's enqueues from the
profiler's own trace, idle gaps charged by cause, and the device time of an
HLO scope. The helper of the readers PR 23 added; ``reduce_xplane.py`` (a
fixed file) does the device side and is not repeated here.

**Where the spans come from.** While the tracer is on, every ``span()`` of
``bigdl_tpu/telemetry/tracing.py`` is also a ``jax.profiler.TraceAnnotation``,
so the slice's xplane holds them in ``/host:CPU`` beside the runtime's
``DoEnqueueProgram`` events: one clock, no anchor. The fixed loader keeps
neither, so this module opens the slice's xplane a second time and reads
the host plane alone (``load_host``). The ring buffer (``ctx["spans"]``)
would have needed the anchor again to meet the enqueues; it is read only
where no enqueue is involved (the serve readers). A program without the
mirrored annotations (any commit before PR 23) has no ``train.*`` event
there: ``host_of`` returns None and every reader built on it returns None.

**Charging a gap by cause** (``charge_gaps``). For each idle gap on a
device, B is the program run whose first operation ends the gap.

- B was already running when the gap began (a gap between two operations
  of one program): ``runtime``. No host code can remove it.
- Otherwise B's ``DoEnqueueProgram`` is looked up by ``run_id`` (and the
  device's ordinal). If B was enqueued before the gap began, the device had
  the program and did not start it: ``runtime`` again.
- Otherwise the gap is the host's: the device waited for an enqueue that
  came late. The WHOLE gap (launch latency after the enqueue included: an
  earlier enqueue would have hidden it) is split over the innermost program
  spans covering the host's time from the gap's beginning to the enqueue,
  by the time each covers; what no span covers is ``none``.
- B has no enqueue event in the trace: ``none``.

Known limit (v5e mesh, PR 23): the runtime enqueues the programs of chips
1-3 only after their previous program has ended, from threads of its own,
although the loop handed them over long before; the rule above then charges
that gap to whatever span the loop's thread is in by then (``train.sync``,
``train.log``). ``PJRT_LoadedExecutable_Execute``, the hand-over, carries
no ``run_id`` to match by.

The gap's beginning is a device timestamp and the enqueue a host one, so
that ONE boundary needs ``rx.device_clock_lag``, which is known to about
the shortest enqueue-to-start latency in the trace (tenths of a
millisecond). A residual error d moves a gap between ``runtime`` and the
host only where the enqueue fell within d of the previous run's end, and
shifts the host interval's start by d, so a span's charge is off by at most
d/(interval) of that gap. Spans and enqueues among themselves share the
host plane's clock and need nothing.

**A scope's device time** (``scope_seconds``). The v5e trace names an
operation by its HLO instruction and carries no ``op_name``; the compiled
HLO in ``ctx["hlo"]`` does (``metadata={op_name="...lm_head_ce/while"}``).
So the instructions under a scope are found in the HLO and their events in
the trace by instruction name, inside runs of the step program only (other
programs reuse names like ``fusion.1``), as a union of intervals: a
``while`` event spans its body's operations, which are not counted twice.
"""

from __future__ import annotations

import bisect
import re
import sys
from dataclasses import dataclass, field

from benchmark import harness, reduce_xplane as rx

RUNTIME, NONE = "runtime", "none"
SPAN_PREFIXES = ("train.", "serving.")
ENQUEUE = "DoEnqueueProgram"


@dataclass
class Host:
    """The host plane, on its own clock (seconds)."""
    spans: list = field(default_factory=list)       # (name, t0, t1, stats)
    enqueues: dict = field(default_factory=dict)    # (run_id, ordinal) -> t0

    def named(self, name):
        return sorted((s for s in self.spans if s[0] == name),
                      key=lambda s: s[1])

    def enqueue_of(self, run_id, ordinal):
        t = self.enqueues.get((run_id, ordinal))
        if t is None:       # a trace that does not say which device
            ts = [v for (r, _), v in self.enqueues.items() if r == run_id]
            t = min(ts) if ts else None
        return t


def load_host(path):
    """The program's mirrored spans and the runtime's enqueues of an xplane
    file."""
    from jax.profiler import ProfileData
    host = Host()
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == ENQUEUE:
                    st = dict(e.stats)
                    key = (st.get("run_id"), st.get("device_ordinal"))
                    t0 = e.start_ns * 1e-9
                    host.enqueues[key] = min(t0, host.enqueues.get(key, t0))
                elif e.name.startswith(SPAN_PREFIXES):
                    t0 = e.start_ns * 1e-9
                    host.spans.append((e.name, t0,
                                       t0 + e.duration_ns * 1e-9,
                                       dict(e.stats)))
    return host


def host_of(ctx):
    """The slice's ``Host``, read once a run; None where there is no device
    trace or the program mirrored no span into it."""
    if "_timeline_host" not in ctx:
        host = None
        if ctx.get("trace") is not None and ctx.get("lo") is not None:
            # the same file the fixed loader read: the slice's newest xplane
            path = harness.TracedSlice(ctx["cell"]["name"]).xplane_path()
            host = load_host(path) if path else None
            if host is not None and not host.spans:
                host = None
        ctx["_timeline_host"] = host
    return ctx["_timeline_host"]


def ordinal(dev):
    m = re.search(r":(\d+)$", dev.name)
    return int(m.group(1)) if m else None


# ------------------------------------------------------------------ charging

def split_by_span(spans, a, b):
    """{span name: seconds} of the host interval [a, b] by the INNERMOST
    span covering each moment (of two covering spans, the one that started
    later); ``none`` for what no span covers."""
    out = {}
    if b <= a:
        return out
    over = [s for s in spans if s[1] < b and s[2] > a]
    cuts = sorted({a, b, *(min(max(t, a), b) for s in over for t in s[1:3])})
    for c0, c1 in zip(cuts, cuts[1:]):
        if c1 - c0 < 1e-12:         # rounding dust between equal cuts
            continue
        mid = (c0 + c1) / 2
        cover = [s for s in over if s[1] <= mid < s[2]]
        name = max(cover, key=lambda s: (s[1], -s[2]))[0] if cover else NONE
        out[name] = out.get(name, 0.0) + (c1 - c0)
    return out


def charge_device(dev, host, lo, hi, lag):
    """{cause: seconds} over the idle gaps of one device inside [lo, hi]
    (device clock); the values add up to the device's idle time there."""
    out = {}
    mods = sorted(dev.modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    n = ordinal(dev)
    for g0, g1 in rx.idle_gaps(dev, lo, hi):
        i = bisect.bisect_right(starts, g1 + 1e-7) - 1
        b = mods[i] if i >= 0 else None
        if b is not None and b[1] < g0 - 1e-7:
            parts = {RUNTIME: 1.0}              # inside a running program
        elif b is None:
            parts = {NONE: 1.0}
        else:
            enq = host.enqueue_of(b[3], n)
            begin = g0 + lag                    # on the host's clock
            if enq is None:
                parts = {NONE: 1.0}
            elif enq <= begin:
                parts = {RUNTIME: 1.0}          # had it, did not start it
            else:
                by = split_by_span(host.spans, begin, enq)
                parts = {k: v / (enq - begin) for k, v in by.items()}
        for k, share in parts.items():
            out[k] = out.get(k, 0.0) + share * (g1 - g0)
    return out


def charge_gaps(ctx):
    """{cause: seconds}, mean over the cell's chips, of the slice's idle
    time; None without the mirrored spans. Read once a run; the split goes
    to standard error for PERF.md."""
    if "_timeline_charge" not in ctx:
        host, out = host_of(ctx), None
        if host is not None:
            trace = ctx["trace"]
            lag = rx.device_clock_lag(trace)
            out = {}
            for dev in trace.devices:
                for k, v in charge_device(dev, host, ctx["lo"], ctx["hi"],
                                          lag).items():
                    out[k] = out.get(k, 0.0) + v / len(trace.devices)
            print("benchmark idle by cause (s, mean over chips): "
                  + ", ".join(f"{k} {v:.6f}" for k, v in
                              sorted(out.items(), key=lambda kv: -kv[1]))
                  + f"; device clock lag {lag * 1e3:.3f} ms",
                  file=sys.stderr)
        ctx["_timeline_charge"] = out
    return ctx["_timeline_charge"]


def iterations(ctx):
    """Iterations the slice holds (a fraction): its length over the run's
    own seconds an iteration, so that a per-iteration idle metric times
    this is the slice's idle time."""
    if not ctx.get("window_s") or not ctx.get("step_seconds"):
        return None
    return ctx["window_s"] / ctx["step_seconds"]


def idle_ms_per_iteration(ctx, pick):
    """Milliseconds of idle an iteration over the causes ``pick(name)``
    accepts; None without the mirrored spans."""
    charge, n = charge_gaps(ctx), iterations(ctx)
    if charge is None or not n:
        return None
    return 1e3 * sum(v for k, v in charge.items() if pick(k)) / n


# ------------------------------------------------------------ epoch boundary

def epoch_boundaries(trace, host, lo, hi, lag):
    """Device-idle seconds between the two runs of the step program on
    either side of each ``train.epoch_end`` span, one value a boundary and
    chip, for the boundaries whose two runs lie in the slice."""
    out = []
    for dev in trace.devices:
        runs = rx.program_runs(dev, lo, hi)
        for _, _, s1, _ in host.named("train.epoch_end"):
            before = [r for r in runs if r[1] + lag < s1]
            after = [r for r in runs if r[1] + lag >= s1]
            if before and after:
                a, b = before[-1], after[0]
                busy = rx.total(rx.busy_intervals(dev, a[2], b[1]))
                out.append(max(0.0, (b[1] - a[2]) - busy))
    return out


# ------------------------------------------------ programs beside the step

def other_program_seconds(trace, lo, hi):
    """Device seconds (mean over chips) of the program runs wholly inside
    the slice that are NOT runs of the step program: in a training cell
    the device cache's index programs, its batch gather and the key split.
    By what runs, not by where it was enqueued: the cache's gather is
    enqueued lazily inside ``train.dispatch``, and on a mesh the runtime
    enqueues the programs of chips 1-3 only after the previous step has
    ended, from threads of its own, whatever span the loop is in by then
    (v5e, PR 23)."""
    if not trace.devices:
        return 0.0
    total = 0.0
    for dev in trace.devices:
        steps = {r[3] for r in rx.program_runs(dev, lo, hi)}
        total += sum(m[2] - m[1] for m in dev.modules
                     if m[3] not in steps and m[1] >= lo and m[2] <= hi)
    return total / len(trace.devices)


# ---------------------------------------------------------------- HLO scopes

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*"
                    r"metadata=\{[^}]*op_name=\"([^\"]*)\"")


def scope_instructions(hlo, scope):
    """Names of the instructions of an HLO text whose ``op_name`` holds
    ``scope`` as one component of its path (``.../lm_head_ce/while``,
    also wrapped by a transformation: ``jvp(lm_head_ce)``)."""
    part = re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:$|[/)])")
    names = set()
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m and part.search(m.group(2)):
            names.add(m.group(1))
    return names


def scope_seconds(trace, names, lo, hi):
    """(device seconds of the operations named ``names`` inside runs of the
    step program, as a union of intervals, mean over chips; runs of the
    step program a chip)."""
    if not trace.devices or not names:
        return 0.0, 0
    total = runs_n = 0.0
    for dev in trace.devices:
        runs = rx.program_runs(dev, lo, hi)
        starts = [r[1] for r in runs]

        def in_a_run(t):
            i = bisect.bisect_right(starts, t) - 1
            return i >= 0 and t < runs[i][2]
        total += rx.total(rx.union(
            (o.t0, o.t1) for o in dev.ops
            if o.name in names and in_a_run(o.t0)))
        runs_n += len(runs)
    return total / len(trace.devices), runs_n / len(trace.devices)


def scope_of(ctx, scope):
    """(seconds, step runs) of ``scope`` in the traced slice, or None where
    the step's HLO has no instruction under it."""
    if ctx.get("trace") is None or ctx.get("lo") is None \
            or not ctx.get("hlo"):
        return None
    names = scope_instructions(ctx["hlo"], scope)
    if not names:
        return None
    sec, runs = scope_seconds(ctx["trace"], names, ctx["lo"], ctx["hi"])
    return (sec, runs) if sec and runs else None
