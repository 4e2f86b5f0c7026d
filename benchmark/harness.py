"""What every kind of cell shares: the files found by name, the device, the
program's counters and spans on the benchmark's clock, the traced slice.

Fixed file: a later PR adds configurations, cells, metrics, kinds and
builders as new files and does not edit this one.
"""

from __future__ import annotations

import glob
import importlib
import json
import os
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
SCRATCH = os.path.join(ROOT, ".bench_scratch")      # traces; git-ignored


class BenchFailure(Exception):
    """The run cannot stand as a measurement; nothing is printed."""


def read_json(*parts):
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def load_manifest():
    return read_json(ROOT, "BENCHMARK.json")


def _merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if (
            isinstance(v, dict) and isinstance(out.get(k), dict)) else v
    return out


def load_cell(name, rehearse=False):
    """``workloads/<name>.json`` and the configuration it names; in a
    rehearsal each file's ``rehearsal`` group overrides its tiny sizes."""
    cell = read_json(HERE, "workloads", name + ".json")
    config = read_json(HERE, "configs", cell["config"] + ".json")
    if rehearse:
        cell = _merge(cell, cell.get("rehearsal", {}))
        config = _merge(config, config.get("rehearsal", {}))
    cell["name"] = name
    return cell, config


def load_kind(name):
    return importlib.import_module(f"benchmark.kinds.{name}")


def load_builder(family):
    return importlib.import_module(f"benchmark.builders.{family}")


def load_peaks(device_kind):
    """The one table of peaks. An unknown device kind is an error."""
    table = read_json(HERE, "peaks.json")
    if device_kind not in table["devices"]:
        raise BenchFailure(f"device kind {device_kind!r} is not in "
                           "benchmark/peaks.json")
    return table["devices"][device_kind]


# ------------------------------------------------------------------ the device

def devices_for(chips, rehearse):
    """The cell's devices, or a failure: nothing falls back to a CPU."""
    import jax
    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        raise BenchFailure(f"no accelerator: jax's backend is "
                           f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchFailure(f"the cell needs {chips} chip(s), jax sees "
                           f"{len(devs)}")
    return devs[:chips]


def live_bytes(devices):
    """The allocator's ``bytes_in_use`` now, on the fullest of the devices;
    0 where the backend keeps no statistics, as the CPU."""
    return max((int((d.memory_stats() or {}).get("bytes_in_use", 0))
                for d in devices), default=0)


def memory_peak_bytes(live, snapshot):
    """What the SYSTEM held on the fullest of the cell's chips while the
    window ran: ``live``, the largest ``live_bytes`` the kind sampled inside
    the window (its opening, its middle, its close), plus the temporaries of
    the largest program the flight recorder compiled
    (``bigdl_program_temp_bytes{site}``, from ``memory_analysis()``: the
    program's own sites only, never the benchmark's reference programs).

    Not the allocator's lifetime ``peak_bytes_in_use``: in the serve cells
    that peak is set by the benchmark's own reference check during set-up
    (6.84 GB there against ~4.2 GB live in the window, PR 22), so memory a
    change adds or saves below it would not show. The allocator counts live
    buffers only (the dp4 ResNet step needs 9.09 GB of temporaries a chip
    beside 1.18 GB of live buffers, PR 22), hence the sum; the temporaries
    are the compiler's figure for the largest program, an upper estimate
    of what the window's programs held at once. 0 where the backend keeps
    no statistics."""
    temps = [v for k, v in snapshot.items()
             if k.startswith("bigdl_program_temp_bytes{")]
    return live + int(max(temps, default=0)) if live else 0


# ------------------------------------------------ the program's own counters

def counters():
    """Snapshot of the program's registry: ``name{k=v,...}`` -> value, and
    for a histogram its ``(sum, count)`` (its quantiles are bucket edges
    and are not read)."""
    from bigdl_tpu.telemetry import get_registry
    out = {}
    for fam in get_registry().collect():
        for s in fam["samples"]:
            labels = ",".join(f"{k}={v}" for k, v in
                              sorted(s.get("labels", {}).items()))
            key = f"{fam['name']}{{{labels}}}"
            if "histogram" in s:
                out[key] = (float(s["histogram"]["sum"]),
                            float(s["histogram"]["count"]))
            elif s.get("value") is not None:
                out[key] = float(s["value"])
    return out


def counter_delta(before, after, name):
    """Rise of every series of family ``name`` between two snapshots,
    summed over its labels: a float, or ``(dsum, dcount)``."""
    total, hist = 0.0, [0.0, 0.0]
    seen = is_hist = False
    for key, val in after.items():
        if key.split("{", 1)[0] != name:
            continue
        seen = True
        old = before.get(key, (0.0, 0.0) if isinstance(val, tuple) else 0.0)
        if isinstance(val, tuple):
            is_hist = True
            hist[0] += val[0] - old[0]
            hist[1] += val[1] - old[1]
        else:
            total += val - old
    if not seen:
        return None
    return tuple(hist) if is_hist else total


# ------------------------------------------------------- the program's spans

class SpanTap:
    """The program's host spans (``telemetry/tracing.py``) for one slice,
    moved onto ``time.perf_counter()`` seconds through one anchor event of
    the benchmark's own (the tracer's time origin is private to it)."""

    def __init__(self, capacity=1 << 20):
        from bigdl_tpu.telemetry import tracing
        self._tracing = tracing
        self._capacity = capacity
        self.t_start = self.t_stop = None

    def start(self):
        tr = self._tracing
        tr.clear()
        tr.enable(self._capacity)
        self.t_start = time.perf_counter()
        tr.complete_event("benchmark.anchor", self.t_start, self.t_start)

    def stop(self):
        tr = self._tracing
        self.t_stop = time.perf_counter()
        tr.disable()
        events = tr.events()
        tr.clear()
        anchor = next((e for e in events
                       if e["name"] == "benchmark.anchor"), None)
        if anchor is None:      # the ring wrapped: nothing can be placed
            return []
        origin = self.t_start - anchor["ts"] / 1e6
        out = []
        for e in events:
            if e["name"] == "benchmark.anchor":
                continue
            out.append({"name": e["name"], "ph": e.get("ph", "X"),
                        "t0": origin + e["ts"] / 1e6,
                        "dur": e.get("dur", 0.0) / 1e6,
                        "args": e.get("args", {}), "id": e.get("id")})
        return out


# ------------------------------------------------------------- the traced slice

SLICE_S = 3.0       # a traced run traces the last seconds of its window


class TracedSlice:
    """One profiler trace of a steady slice with the program's spans beside
    it. An anchor annotation ties the trace's clock to
    ``time.perf_counter()``."""

    ANCHOR = "benchmark.anchor"

    def __init__(self, tag):
        self.dir = os.path.join(SCRATCH, "trace", tag)
        self.spans = SpanTap()
        self.events = []
        self.t_anchor = self.t_start = self.t_stop = None

    @staticmethod
    def starts_at(t_close, seconds):
        return t_close - min(SLICE_S, seconds / 2)

    @property
    def started(self):
        return self.t_start is not None

    def start(self):
        import shutil

        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # the python tracer slows the host
        opts.host_tracer_level = 2
        self.spans.start()
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_anchor = time.perf_counter()
        with jax.profiler.TraceAnnotation(self.ANCHOR):
            time.sleep(0.0005)
        self.t_start = time.perf_counter()

    def stop(self):
        import jax
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        self.events = self.spans.stop()

    @property
    def wall_s(self):
        """Seconds the program's spans were recorded for."""
        return self.spans.t_stop - self.spans.t_start if self.started \
            and self.spans.t_stop else None

    def xplane_path(self):
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


# ---------------------------------------------------------- per-layer readers

def layer_metric_values(manifest, cell_name, e2e_names, ctx):
    """For every per-layer entry of the manifest that this cell reports
    (it lists the cell, or lists none, and the metric it moves is one of
    the cell's), call its reader ``layer_metrics/<reader>.py`` — the part
    of the entry's name before the first ``.`` — and keep what it finds.
    A reader that finds nothing to read returns None and is left out."""
    out, cache = {}, {}
    for entry in manifest["per_layer"]:
        if "workloads" in entry and cell_name not in entry["workloads"]:
            continue
        if entry["moves"] not in e2e_names:
            continue
        reader = entry["name"].split(".", 1)[0]
        if reader not in cache:
            mod = importlib.import_module(f"benchmark.layer_metrics.{reader}")
            cache[reader] = mod.read(ctx)
        if cache[reader] is not None:
            out[entry["name"]] = {"value": float(cache[reader]),
                                  "unit": entry["unit"]}
    return out


def cell_e2e_entries(manifest, cell_name):
    return [m for m in manifest["end_to_end"]
            if "workloads" not in m or cell_name in m["workloads"]]
