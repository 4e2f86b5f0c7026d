"""Compile flight recorder: ``tracked_jit`` — a ``jax.jit`` wrapper that
ATTRIBUTES cost instead of just spending it.

PR 5's telemetry records durations; nothing said which jitted programs
compiled, how long each compilation took, what FLOPs/HBM bytes a program
accounts for, or what memory it holds. This module closes that gap with
one primitive every jitted site adopts (``optim/optimizer.py``,
``parallel/distri_optimizer.py``, ``models/serving.py``,
``models/generation.py``, ``optim/evaluator.py``):

    step = tracked_jit(step_fn, site="train.step", donate_argnums=(0, 1, 2))

Mechanics: the wrapper keys calls by the ABSTRACT argument signature
(pytree structure + per-leaf shape/dtype/sharding — exactly what XLA
specializes on) and compiles new signatures through the AOT path
(``jitted.lower(*args).compile()``), so each compilation happens exactly
once, is timed on the wall clock, and yields the compiled executable's
``cost_analysis()`` (FLOPs, bytes accessed) and ``memory_analysis()``
(temp/output bytes) BEFORE the first execution. Repeat calls dispatch the
cached executable directly. One flight-recorder event per compilation
lands in:

- ``bigdl_compiles_total{site}`` / ``bigdl_compile_seconds{site}``;
- per-site last-program cost gauges ``bigdl_program_flops{site}``,
  ``bigdl_program_bytes_accessed{site}``, ``bigdl_program_temp_bytes``
  ``/_output_bytes{site}``;
- a ``profiling.compile`` span (site + signature + seconds) when the
  tracer is on, so compile storms are visible inside a Chrome trace.

Cost fields are present-or-None: backends that cannot answer (some CPU
builds, PJRT plugins without analysis support) degrade to counting and
timing only. A compilation error propagates to the caller from the
``lower().compile()`` that raised it; the only call that bypasses the
recorder is one made with tracer arguments (inside another trace), which
cannot be lowered ahead of time.

The per-signature executable cache is bounded (``cache_size``) with
OLDEST-FIRST SINGLE-ENTRY eviction — evicting one program on overflow
instead of wiping the cache, so live signatures under mixed traffic do
not all recompile at once (the clear-at-cap eviction storm this PR fixes
in the serving prefill and generate() caches). Evictions count in
``bigdl_compile_cache_evictions_total{site}``.

jax-free at import (the telemetry package contract): jax loads on first
``tracked_jit`` construction.
"""

from __future__ import annotations

import functools
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Tuple

from bigdl_tpu.telemetry.registry import MetricsRegistry, get_registry
from bigdl_tpu.telemetry.tracing import span

__all__ = ["tracked_jit", "TrackedJit", "CompileEvent", "peak_flops",
           "kind_peak_flops", "require_tpu", "sample_device_memory",
           "DEFAULT_CACHE_SIZE"]

#: Default retained-executable bound per tracked site. Generous for
#: steady-state sites (a training loop has ONE signature) and for the
#: O(1)/O(log) program families the chunked/bucketed serving prefill
#: dispatches through a single wrapper.
DEFAULT_CACHE_SIZE = 64


class CompileEvent:
    """One recorded compilation: what compiled, how long, what it costs."""

    __slots__ = ("site", "signature", "seconds", "flops", "bytes_accessed",
                 "temp_bytes", "output_bytes", "argument_bytes")

    def __init__(self, site: str, signature: str, seconds: float,
                 flops: Optional[float] = None,
                 bytes_accessed: Optional[float] = None,
                 temp_bytes: Optional[int] = None,
                 output_bytes: Optional[int] = None,
                 argument_bytes: Optional[int] = None):
        self.site = site
        self.signature = signature
        self.seconds = seconds
        self.flops = flops
        self.bytes_accessed = bytes_accessed
        self.temp_bytes = temp_bytes
        self.output_bytes = output_bytes
        self.argument_bytes = argument_bytes

    def as_dict(self) -> dict:
        return {k: getattr(self, k) for k in self.__slots__}


def _leaf_key(x, with_sharding: bool) -> Tuple:
    """Hashable abstract descriptor of one argument leaf. jax arrays key
    on (shape, dtype, weak_type, sharding) — sharding included because a
    compiled executable is specialized to its input layout (a mesh-
    committed and an uncommitted array of the same shape need different
    programs) — unless the jit fixes that layout itself
    (``with_sharding=False``, see ``TrackedJit.__init__``). Non-array
    leaves key on their type: a Python scalar traces as a weak-typed 0-d
    input, so its VALUE does not split programs.

    TRACER leaves raise TypeError: a tracked fn called inside another
    trace (the eval scorer calls the tracked forward) must inline through
    the plain jit wrapper — a compiled executable cannot consume
    tracers. ``__call__`` catches and dispatches accordingly."""
    import jax
    if isinstance(x, jax.core.Tracer):
        raise TypeError("tracer argument: dispatch through jax.jit")
    aval = getattr(x, "aval", None)
    if aval is not None:                       # jax.Array fast path
        return (aval.shape, str(aval.dtype), bool(aval.weak_type),
                getattr(x, "sharding", None) if with_sharding else None)
    shape = getattr(x, "shape", None)
    if shape is not None and hasattr(x, "dtype"):   # numpy array
        return (tuple(shape), str(x.dtype), False, None)
    return (type(x),)


def _cost_number(analysis, key: str) -> Optional[float]:
    """Pull one scalar out of ``Compiled.cost_analysis()`` across the API
    shapes jax has shipped: a dict, or a list with one dict per
    computation (sum them — a multi-computation program spends all of
    them per call)."""
    if analysis is None:
        return None
    if isinstance(analysis, dict):
        analysis = [analysis]
    total, seen = 0.0, False
    try:
        for entry in analysis:
            v = entry.get(key)
            if v is not None and v >= 0:
                total += float(v)
                seen = True
    except (AttributeError, TypeError):
        return None
    return total if seen else None


def _named_after(fn: Callable, site: str) -> Callable:
    """``fn`` under the name of its site (``train.step`` -> ``train_step``),
    which jax gives the compiled program: ``HloModule jit_train_step``, the
    profile's ``XLA Modules`` events, the ``jit(train_step)/`` every
    ``op_name`` starts with. The name is also part of the persistent
    compile cache's key, and ``op_name`` metadata is NOT (jax 0.9.0 strips
    it before hashing): programs that differ only in their scopes share a
    key, so a cache an older checkout filled would serve a step whose text
    carries that checkout's scopes (``telemetry/step_partition.py`` reads
    them). A site's name changes the key with the site."""
    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = site.replace(".", "_")
    return program


class TrackedJit:
    """``jax.jit`` with a compile flight recorder (see module docstring).
    The compiled program carries its SITE's name (``_named_after``).

    NOT a drop-in for every jit feature: static_argnums/argnames are
    passed through to the underlying jit, but the signature key treats
    Python scalars by TYPE, so static-arg call families should keep using
    plain ``jax.jit`` (graftlint JG013 already polices those). All
    adopted sites in this repo take array pytrees only.
    """

    def __init__(self, fn: Callable, *, site: str,
                 registry: Optional[MetricsRegistry] = None,
                 cache_size: int = DEFAULT_CACHE_SIZE,
                 **jit_kwargs):
        import jax

        from bigdl_tpu.telemetry.catalogue import instruments
        self.site = site
        self.cache_size = max(1, int(cache_size))
        self._jitted = jax.jit(_named_after(fn, site), **jit_kwargs)
        # explicit in_shardings fix the program's input layout, so the
        # arguments' own placement must not split programs: a mesh step's
        # first call sees fresh single-device state and every later call
        # the mesh-committed outputs of the one before — one program, not
        # two (and a third when the loop swaps in a fresh epoch scalar).
        # The executable reshards uncommitted arguments itself and rejects
        # a committed argument laid out otherwise.
        self._key_on_sharding = "in_shardings" not in jit_kwargs
        self._registry = registry if registry is not None else get_registry()
        self._tm = instruments(self._registry)
        self._programs: "OrderedDict[Tuple, Any]" = OrderedDict()
        self.events: list = []            # CompileEvent, oldest first
        self.last_event: Optional[CompileEvent] = None
        self.compiles = 0

    # ------------------------------------------------------------- recording
    @property
    def last_flops(self) -> Optional[float]:
        ev = self.last_event
        return ev.flops if ev is not None else None

    def _signature(self, args) -> Tuple:
        import jax
        leaves, treedef = jax.tree_util.tree_flatten(args)
        with_sharding = self._key_on_sharding
        return (treedef, tuple(_leaf_key(x, with_sharding) for x in leaves))

    def _describe(self, args) -> str:
        """Human-readable shape signature for the event/span (kept terse:
        leaf count + first few leaf shapes)."""
        import jax
        leaves = jax.tree_util.tree_leaves(args)
        shapes = []
        for x in leaves[:4]:
            shapes.append("x".join(str(d) for d in getattr(x, "shape", ()))
                          or "scalar")
        extra = f"+{len(leaves) - 4}" if len(leaves) > 4 else ""
        return f"{len(leaves)} leaves ({','.join(shapes)}{extra})"

    def _record(self, seconds: float, compiled, signature: str) -> None:
        flops = bytes_accessed = temp = outb = argb = None
        try:
            analysis = compiled.cost_analysis()
            flops = _cost_number(analysis, "flops")
            bytes_accessed = _cost_number(analysis, "bytes accessed")
        except Exception:       # noqa: BLE001 — analysis is best-effort
            pass
        try:
            mem = compiled.memory_analysis()
            temp = int(getattr(mem, "temp_size_in_bytes", None))
            outb = int(getattr(mem, "output_size_in_bytes", None))
            argb = int(getattr(mem, "argument_size_in_bytes", None))
        except Exception:       # noqa: BLE001
            pass
        ev = CompileEvent(self.site, signature, seconds, flops,
                          bytes_accessed, temp, outb, argb)
        self.events.append(ev)
        self.last_event = ev
        self.compiles += 1
        site = self.site
        self._tm.compiles_total.labels(site=site).inc()
        self._tm.compile_seconds.labels(site=site).observe(seconds)
        if flops is not None:
            self._tm.program_flops.labels(site=site).set(flops)
        if bytes_accessed is not None:
            self._tm.program_bytes_accessed.labels(site=site).set(
                bytes_accessed)
        if temp is not None:
            self._tm.program_temp_bytes.labels(site=site).set(temp)
        if outb is not None:
            self._tm.program_output_bytes.labels(site=site).set(outb)

    # ------------------------------------------------------------- dispatch
    def __call__(self, *args):
        programs = self._programs
        try:
            key = self._signature(args)
        except TypeError:         # tracer arguments: inline through jit
            return self._jitted(*args)
        compiled = programs.get(key)
        if compiled is None:
            compiled = self._compile(key, args)
        else:
            programs.move_to_end(key)
        return compiled(*args)

    def _compile(self, key, args):
        """AOT-compile a new signature, record the event, bound the cache.
        A compile error (Mosaic, HBM) propagates from here, once."""
        desc = self._describe(args)
        t0 = time.perf_counter()
        with span("profiling.compile", site=self.site, signature=desc):
            compiled = self._jitted.lower(*args).compile()
        self._record(time.perf_counter() - t0, compiled, desc)
        self._programs[key] = compiled
        self._evict()
        return compiled

    def _evict(self) -> None:
        while len(self._programs) > self.cache_size:
            # oldest-first SINGLE-entry eviction — never clear-at-cap
            # (evicting everything forces every live signature to
            # recompile immediately; see module docstring)
            self._programs.popitem(last=False)
            self._tm.compile_cache_evictions_total.labels(
                site=self.site).inc()

    # -------------------------------------------------------------- AOT API
    def lower(self, *args, **kwargs):
        """Delegate to the underlying ``jax.jit`` wrapper (HLO-contract
        tests lower and inspect programs without executing them)."""
        return self._jitted.lower(*args, **kwargs)

    def compiled_texts(self) -> list:
        """Optimized-HLO text of every retained executable, oldest first:
        the programs that actually ran (``chip_smoke.py`` looks there for
        the Mosaic custom calls and the all-reduce)."""
        return [c.as_text() for c in self._programs.values()]

    def __repr__(self) -> str:
        return (f"TrackedJit(site={self.site!r}, compiles={self.compiles}, "
                f"cached={len(self._programs)})")


def tracked_jit(fn: Callable, *, site: str,
                registry: Optional[MetricsRegistry] = None,
                cache_size: int = DEFAULT_CACHE_SIZE,
                **jit_kwargs) -> TrackedJit:
    """Wrap ``fn`` as a compile-tracked jit (see :class:`TrackedJit`)."""
    return TrackedJit(fn, site=site, registry=registry,
                      cache_size=cache_size, **jit_kwargs)


# ---------------------------------------------------------------------------
# Peak-FLOPs model + MFU
# ---------------------------------------------------------------------------

# The one peak table: bf16 peak FLOP/s of one chip by ``device_kind``
# substring, first match wins (Google Cloud TPU documentation, per-chip
# figures; a v5e reports itself as "TPU v5 lite"). chip_smoke.py and the
# live MFU gauge read it; a kind that is not here has no MFU.
_PEAK_BY_KIND = (
    ("v5 lite", 197e12), ("v5e", 197e12),
    ("v5p", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
    ("v6", 918e12),
)

_peak_cache: Dict[str, Optional[float]] = {}


def kind_peak_flops(device_kind: str) -> Optional[float]:
    """The table's bf16 peak for a ``device_kind`` string, or None."""
    kind = device_kind.lower()
    return next((f for sub, f in _PEAK_BY_KIND if sub in kind), None)


def require_tpu():
    """Gate of the measurement paths (``chip_smoke.py``, the benchmark):
    returns ``(device, peak_flops)`` for the first device, and raises
    unless it is a TPU whose kind the peak table knows — a number taken
    anywhere else is not a device metric."""
    import jax
    dev = jax.devices()[0]
    peak = kind_peak_flops(dev.device_kind) if dev.platform == "tpu" else None
    if peak is None:
        raise RuntimeError(
            f"needs a TPU the peak table knows: jax's default backend is "
            f"{jax.default_backend()!r}, device kind {dev.device_kind!r}")
    return dev, peak


def peak_flops() -> Optional[float]:
    """Per-chip peak FLOP/s for the live MFU gauge: the table's entry for
    this process's first device, None off-TPU or for an unknown kind — an
    MFU computed against a made-up roof is worse than no MFU."""
    if "kind" not in _peak_cache:
        import jax
        dev = jax.local_devices()[0]
        _peak_cache["kind"] = (kind_peak_flops(dev.device_kind)
                               if dev.platform == "tpu" else None)
    return _peak_cache["kind"]


def mfu(flops_per_step: Optional[float],
        step_seconds: float) -> Optional[float]:
    """Model-FLOPs utilization: cost-analysis FLOPs / wall seconds /
    peak. None whenever either input is unknown."""
    peak = peak_flops()
    if not flops_per_step or not step_seconds or not peak:
        return None
    return flops_per_step / step_seconds / peak


# ---------------------------------------------------------------------------
# Device-memory watermark
# ---------------------------------------------------------------------------

_mem_unsupported = False


def sample_device_memory(registry: Optional[MetricsRegistry] = None) -> \
        Optional[int]:
    """Sample every local device's memory stats and publish the FULLEST
    (most ``bytes_in_use``; its peak beside it) into the
    ``bigdl_device_memory_bytes`` / ``_peak_bytes`` gauges: on a mesh the
    chip that runs out first is the one that matters, and device 0 is not
    always it. Returns that peak, or None where the runtime has no
    allocator stats (CPU). Called at step boundaries and slot admission —
    one PJRT call a device, and a no-op forever after the first
    unsupported answer."""
    global _mem_unsupported
    if _mem_unsupported:
        return None
    try:
        import jax
        stats = [d.memory_stats() for d in jax.local_devices()]
    except Exception:       # noqa: BLE001 — absent backend == unsupported
        stats = []
    stats = [s for s in stats if s]
    if not stats:
        _mem_unsupported = True
        return None
    from bigdl_tpu.telemetry.catalogue import instruments
    tm = instruments(registry if registry is not None else get_registry())
    fullest = max(stats, key=lambda s: s.get("bytes_in_use") or 0)
    in_use = fullest.get("bytes_in_use")
    peak = fullest.get("peak_bytes_in_use")
    if in_use is not None:
        tm.device_memory_bytes.set(in_use)
    if peak is not None:
        tm.device_memory_peak_bytes.set(peak)
    return peak
