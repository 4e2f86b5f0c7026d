"""bigdl_tpu.telemetry — unified runtime observability.

One subsystem answers the operator's first three questions (what is my
TTFT, where does a training step spend its wall-clock, is the queue
backing up) instead of per-module ad-hoc counters:

- ``registry``: thread-safe labeled counters / gauges / fixed-bucket
  histograms (``get_registry()`` is the process-global instance);
- ``exposition``: Prometheus text 0.0.4 + JSON, served as ``GET
  /metrics`` by the serving HTTP rim (``models/lm_server.py``);
- ``tracing``: ``span("name")`` -> bounded ring buffer -> Chrome
  ``trace_event`` JSON, disabled-by-default at one-branch cost;
- ``catalogue``: the well-known metric/span inventory every instrumented
  subsystem builds from (rendered into ``docs/API.md``);
- ``profiling``: the compile flight recorder — ``tracked_jit(site=...)``
  records one event (wall seconds + cost/memory analysis) per program
  compilation at every adopted jit site;
- ``scoreboard``: the automated serving scoreboard (seeded Zipf workload
  driver, /metrics scrape, markdown table, regression diff).

jax-free by design: importable from the CLI
(``python -m bigdl_tpu.telemetry``) and the launcher subcommands
(``scripts/bigdl-tpu.sh metrics|trace|scoreboard``) without touching a
backend (``profiling``/``scoreboard`` lazy-import jax only when a
program is actually wrapped / a workload actually driven).
Guide: ``docs/OBSERVABILITY.md``.
"""

from bigdl_tpu.telemetry.registry import (Counter, CounterFamily, Gauge,
                                          GaugeFamily, Histogram,
                                          HistogramFamily, MetricSpec,
                                          MetricsRegistry,
                                          DEFAULT_LATENCY_BUCKETS,
                                          get_registry, set_registry)
from bigdl_tpu.telemetry.exposition import (PROMETHEUS_CONTENT_TYPE,
                                            render_json, render_prometheus)
from bigdl_tpu.telemetry import profiling, scoreboard, tracing
from bigdl_tpu.telemetry.tracing import span
from bigdl_tpu.telemetry.catalogue import (METRIC_SPECS, SCOPE_SPECS,
                                           SPAN_SPECS, instruments)
from bigdl_tpu.telemetry.profiling import (CompileEvent, TrackedJit,
                                           peak_flops,
                                           sample_device_memory,
                                           tracked_jit)

__all__ = [
    "MetricsRegistry", "MetricSpec", "Counter", "Gauge", "Histogram",
    "CounterFamily", "GaugeFamily", "HistogramFamily",
    "DEFAULT_LATENCY_BUCKETS", "get_registry", "set_registry",
    "render_prometheus", "render_json", "PROMETHEUS_CONTENT_TYPE",
    "tracing", "span", "METRIC_SPECS", "SPAN_SPECS", "SCOPE_SPECS",
    "instruments",
    "profiling", "scoreboard", "tracked_jit", "TrackedJit",
    "CompileEvent", "peak_flops", "sample_device_memory",
]
