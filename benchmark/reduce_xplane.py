"""From a profiler trace (``*.xplane.pb``) to the numbers the per-layer
metrics read: busy and idle time of each device, time per operation,
the idle gaps, the time of collectives that no compute hides, and the
offset between the trace's clock and ``time.perf_counter()``.

What a TPU v5e trace of jax 0.9.0 / libtpu 0.0.34 holds (looked at by hand,
PR 22): one plane ``/device:TPU:<n>`` a chip with the lines ``XLA Modules``
(one event a program run, carrying ``run_id``), ``XLA Ops`` (one event an
HLO instruction, named by the instruction's whole text ``%name = type
op(...)``; a Mosaic kernel is a ``custom-call`` whose text holds
``custom_call_target="tpu_custom_call"`` and is named after the enclosing
jit, not after the kernel) and ``Async XLA Ops`` (the start..done span of
asynchronous copies and collectives); one plane ``/host:CPU`` with a line a
thread, where ``TraceAnnotation``s and the runtime's ``DoEnqueueProgram`` /
``CompleteCallbacks`` events (both carrying ``run_id``) sit. Device
timestamps ran 1.4-1.8 ms behind the host's in that trace, so the two are
tied through ``run_id``: a program starts on the device after its enqueue
and ends before its completion callback.

Fixed file; checked by ``tests/test_reduce_xplane.py`` on a trace recorded
on the chip and on hand-built cases.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from functools import cached_property

MOSAIC = 'custom_call_target="tpu_custom_call"'
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
CONTAINERS = ("while", "conditional", "call")   # events that span their body's
_NAME = re.compile(r"^%?([^\s=]+)")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


@dataclass
class Op:
    name: str           # the instruction's name, "fusion.12"
    text: str           # the whole event name (HLO text on a TPU)
    t0: float           # seconds, trace clock
    t1: float

    @property
    def dur(self):
        return self.t1 - self.t0

    @cached_property
    def opcode(self):
        """``fusion``, ``custom-call``, ``all-reduce-start``... ('' if the
        event's name is not HLO text)."""
        if " = " not in self.text:
            return ""
        m = _OPCODE.search(self.text.split(" = ", 1)[1])
        return m.group(1) if m else ""

    @property
    def is_mosaic(self):
        return MOSAIC in self.text

    @cached_property
    def collective(self):
        code = self.opcode
        for c in COLLECTIVES:
            if code == c or code.startswith(c + "-"):
                return c
        base = self.name.split(".")[0]
        for c in COLLECTIVES:       # fused forms keep the name
            if base.startswith(c):
                return c
        return None


@dataclass
class DevicePlane:
    name: str
    ops: list = field(default_factory=list)         # XLA Ops
    modules: list = field(default_factory=list)     # (name, t0, t1, run_id)
    async_ops: list = field(default_factory=list)   # Async XLA Ops


@dataclass
class Trace:
    devices: list
    host: list          # (name, t0, t1, stats) of the host plane's events
    anchor_t0: float = None     # trace-clock start of the anchor annotation


def op_name(text):
    m = _NAME.match(text.strip())
    return m.group(1) if m else text


def load(path, anchor="benchmark.anchor"):
    """Read an xplane file with nothing but jax."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host, anchor_t0 = [], [], None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = DevicePlane(plane.name)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        t0 = e.start_ns * 1e-9
                        dev.ops.append(Op(op_name(e.name), e.name, t0,
                                          t0 + e.duration_ns * 1e-9))
                elif line.name == "XLA Modules":
                    for e in line.events:
                        t0 = e.start_ns * 1e-9
                        run_id = dict(e.stats).get("run_id")
                        dev.modules.append((e.name, t0,
                                            t0 + e.duration_ns * 1e-9, run_id))
                elif line.name == "Async XLA Ops":
                    for e in line.events:
                        t0 = e.start_ns * 1e-9
                        dev.async_ops.append(Op(op_name(e.name), e.name, t0,
                                                t0 + e.duration_ns * 1e-9))
            dev.ops.sort(key=lambda o: o.t0)
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    t0 = e.start_ns * 1e-9
                    if e.name == anchor:
                        anchor_t0 = t0 if anchor_t0 is None else anchor_t0
                    elif e.name in ("DoEnqueueProgram", "CompleteCallbacks"):
                        host.append((e.name, t0, t0 + e.duration_ns * 1e-9,
                                     dict(e.stats).get("run_id")))
    devices.sort(key=lambda d: d.name)
    return Trace(devices, host, anchor_t0)


# ------------------------------------------------------------------ intervals

def union(intervals):
    """Merged, sorted intervals of a list of (t0, t1)."""
    out = []
    for t0, t1 in sorted(i for i in intervals if i[1] > i[0]):
        if out and t0 <= out[-1][1]:
            if t1 > out[-1][1]:
                out[-1][1] = t1
        else:
            out.append([t0, t1])
    return [(a, b) for a, b in out]


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals):
    return sum(b - a for a, b in intervals)


def subtract(intervals, holes):
    """Parts of merged ``intervals`` not covered by merged ``holes`` (both
    sorted; one pass)."""
    out, holes, j = [], list(holes), 0
    for a, b in intervals:
        cur = a
        while j < len(holes) and holes[j][1] <= cur:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < b:
            h0, h1 = holes[k]
            if h0 > cur:
                out.append((cur, h0))
            cur = max(cur, h1)
            if cur >= b:
                break
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


# ----------------------------------------------------------------- reductions

def busy_intervals(dev, lo=None, hi=None):
    """Union of the intervals in which an operation ran on the device."""
    iv = union((o.t0, o.t1) for o in dev.ops)
    if lo is not None:
        iv = clip(iv, lo, hi)
    return iv


def slice_bounds(trace):
    """The widest slice every device has operations in: from the latest
    first operation to the earliest last one."""
    los = [d.ops[0].t0 for d in trace.devices if d.ops]
    his = [max(o.t1 for o in d.ops) for d in trace.devices if d.ops]
    if not los:
        return None
    return max(los), min(his)


def busy_and_window(trace, lo, hi):
    """(busy seconds averaged over the devices, window seconds)."""
    if not trace.devices or hi <= lo:
        return 0.0, 0.0
    busy = [total(busy_intervals(d, lo, hi)) for d in trace.devices]
    return sum(busy) / len(busy), hi - lo


def idle_gaps(dev, lo, hi):
    """The device's idle intervals inside [lo, hi], longest first."""
    gaps = subtract([(lo, hi)], busy_intervals(dev, lo, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def op_sums(dev, lo, hi, key=lambda o: o.name):
    """{key(op): [seconds, calls]} over the operations that start in the
    slice (seconds are each operation's own duration)."""
    out = {}
    for o in dev.ops:
        if lo <= o.t0 < hi and o.opcode not in CONTAINERS:
            s = out.setdefault(key(o), [0.0, 0])
            s[0] += o.dur
            s[1] += 1
    return out


def program_runs(dev, lo, hi, name_part=None):
    """Runs of one program (``XLA Modules`` events whose name holds
    ``name_part``; the program with most device time if None) that lie
    wholly inside the slice, sorted by start."""
    runs = [m for m in dev.modules if m[1] >= lo and m[2] <= hi]
    if name_part is None and runs:
        by = {}
        for m in runs:
            base = m[0].split("(")[0]
            by[base] = by.get(base, 0.0) + (m[2] - m[1])
        name_part = max(by, key=by.get)
    return sorted((m for m in runs if name_part and name_part in m[0]),
                  key=lambda m: m[1])


def exposed_collective_seconds(dev, lo, hi):
    """Seconds inside [lo, hi] in which a collective is in flight on the
    device and no other operation runs on it. A collective's interval is
    its synchronous op, or the start..done span of its asynchronous form
    (``Async XLA Ops``; its -done instruction is the wait on the wire); a
    collective's own instructions and the events that span a loop's body
    do not count as compute."""
    coll, compute = [], []
    for o in dev.ops:
        if o.collective:
            if not o.opcode.endswith("-start"):
                coll.append((o.t0, o.t1))
        elif o.opcode not in CONTAINERS:
            compute.append((o.t0, o.t1))
    for o in dev.async_ops:
        if o.collective:
            coll.append((o.t0, o.t1))
    coll = clip(union(coll), lo, hi)
    compute = clip(union(compute), lo, hi)
    return total(subtract(coll, compute)), total(coll)


def device_clock_lag(trace):
    """Seconds to ADD to a device timestamp to place it on the host plane's
    clock: each program starts after its ``DoEnqueueProgram`` and ends
    before its ``CompleteCallbacks`` (matched by ``run_id``), which bounds
    the lag from both sides; the middle is returned (0.0 if the trace has
    no such pairs)."""
    enq = {r: t0 for n, t0, _, r in trace.host if n == "DoEnqueueProgram"}
    done = {r: t0 for n, t0, _, r in trace.host if n == "CompleteCallbacks"}
    lows, highs = [], []
    for dev in trace.devices:
        for _, t0, t1, run_id in dev.modules:
            if run_id in enq:
                lows.append(enq[run_id] - t0)
            if run_id in done:
                highs.append(done[run_id] - t1)
    if not lows and not highs:
        return 0.0
    if not highs:
        return max(lows)
    if not lows:
        return min(highs)
    lo, hi = max(lows), min(highs)
    return (lo + hi) / 2 if lo <= hi else lo


def to_perf_counter(trace, t_anchor_perf):
    """A function from a DEVICE timestamp of the trace to
    ``time.perf_counter()`` seconds (None without the anchor)."""
    if trace.anchor_t0 is None or t_anchor_perf is None:
        return None
    lag = device_clock_lag(trace)
    return lambda t: t + lag - trace.anchor_t0 + t_anchor_perf


def attribute_gaps(gaps, spans, to_perf, names, top=10):
    """The longest idle gaps by the program span the host was in at the
    middle of each: [[span name or "none", seconds], ...]."""
    by = {}
    for g0, g1 in gaps:
        label = "none"
        if to_perf is not None:
            mid = to_perf((g0 + g1) / 2)
            best = None
            for s in spans:
                if (s["ph"] == "X" and s["name"] in names
                        and s["t0"] <= mid <= s["t0"] + s["dur"]):
                    if best is None or s["dur"] < best["dur"]:
                        best = s        # the innermost span
            if best is not None:
                label = best["name"]
        by[label] = by.get(label, 0.0) + (g1 - g0)
    return [[k, v] for k, v in sorted(by.items(),
                                      key=lambda kv: -kv[1])][:top]


def top_ops(trace, lo, hi, top=10):
    """[[operation name, seconds], ...] by device time, averaged over the
    devices; instructions of one kind share a name up to the ``.N``."""
    acc = {}
    for dev in trace.devices:
        for name, (sec, _) in op_sums(dev, lo, hi,
                                      key=lambda o: family(o)).items():
            acc[name] = acc.get(name, 0.0) + sec / len(trace.devices)
    return [[k, v] for k, v in sorted(acc.items(),
                                      key=lambda kv: -kv[1])][:top]


def family(op):
    """A name that survives renumbering: Mosaic kernels by their target,
    everything else by the instruction name without its ``.N``."""
    base = re.sub(r"\.\d+$", "", op.name)
    return f"mosaic:{base}" if op.is_mosaic else base
