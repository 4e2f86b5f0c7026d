"""Batch evaluation / prediction drivers (reference ``optim/Evaluator.scala:37``,
``optim/Predictor.scala:34``).

The reference broadcasts the model to executors and mapPartitions over the
RDD; here a single jitted forward is reused across batches (and sharded over
the mesh by ``parallel.distri_optimizer`` when one is active).
``evaluate_batches`` is the one batch-eval/merge loop — Evaluator, Predictor
and in-training validation all delegate to it.
"""

from __future__ import annotations

from typing import Callable, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.dataset.base import (AbstractDataSet, LocalDataSet, MiniBatch,
                                    Sample, SampleToBatch)
from bigdl_tpu.nn.module import Module, functional_apply
from bigdl_tpu.optim.validation import ValidationMethod, ValidationResult
from bigdl_tpu.telemetry import get_registry, instruments, span
from bigdl_tpu.telemetry.profiling import tracked_jit


def _as_minibatch(item) -> MiniBatch:
    if isinstance(item, Sample):
        return MiniBatch(item.feature[None], jnp.atleast_1d(item.label))
    return item


def evaluate_batches(fwd: Callable, params, buffers,
                     batches: Iterable,
                     v_methods: Sequence[ValidationMethod],
                     cache: Optional[dict] = None,
                     ) -> Tuple[List[Optional[ValidationResult]], int]:
    """Run ``fwd(params, buffers, data)`` over batches, merging each method's
    ValidationResults. Returns (results, record_count).

    A tail batch smaller than the first-seen batch is zero-padded up to the
    static shape before ``fwd`` (XLA would otherwise compile a second
    program for the one odd shape) and the padded rows are sliced off the
    output before scoring — every record is evaluated, none double-counted.

    Telemetry: ``bigdl_eval_batches_total`` / ``bigdl_eval_records_total``
    counters + a per-batch host wall-clock histogram land in the global
    registry; the whole call traces as one ``eval.batches`` span.
    """
    with span("eval.batches", methods=len(v_methods)):
        return _evaluate_batches(fwd, params, buffers, batches, v_methods,
                                 cache)


def _evaluate_batches(fwd, params, buffers, batches, v_methods, cache):
    import time
    tm = instruments(get_registry())
    results: List[Optional[ValidationResult]] = [None] * len(v_methods)
    count = 0
    full_bs: Optional[int] = None
    sliceable: Optional[bool] = None  # learned from the first (full) batch
    # Device-side accumulation (steady state): one jitted dispatch per
    # batch carries a donated (M, 2) [value, count] accumulator — the
    # per-batch ``float(v)`` host syncs otherwise dominate eval where
    # dispatch latency binds (each sync is a full device round trip).
    # Callers that evaluate repeatedly (the training loop's validation
    # trigger) pass a persistent ``cache`` dict so the scorer jit is traced
    # ONCE, not per validation (a per-call retrace costs seconds and undoes
    # the win).
    # The fast path jits each method's pure device core. A custom subclass
    # that overrides only apply() (the old per-batch contract) has no such
    # core — run the whole loop on the compatible eager path for it.
    from bigdl_tpu.optim.validation import ValidationMethod as _VM
    fast_ok = all(type(m).batch_result is not _VM.batch_result
                  for m in v_methods)
    # id()-keyed: exact and collision-safe (the cached closure pins the
    # objects alive). Callers constructing FRESH method instances per call
    # miss the cache and pay a retrace — reuse method objects across
    # evaluations (the training loop's validation path does).
    cache_key = (id(fwd),) + tuple(id(m) for m in v_methods)
    scorer = (cache or {}).get(cache_key)
    scorer_cached = scorer is not None
    if fast_ok and scorer is None:
        # built ONCE before the batch loop (graftlint JG004: a jax.jit
        # call inside the loop — even lazily guarded — is the
        # recompile-churn shape; tracing still happens at first use).
        # The CACHE insert stays lazy (first fast-path batch): evicting a
        # valid entry for a scorer that never runs would cost the next
        # evaluation its cached trace.
        def scorer_fn(p, b, x, y, a):
            out = fwd(p, b, x)
            av, ac = a
            # values accumulate f32 (per-batch sums are f32 device
            # results anyway); counts accumulate int32 — EXACT to
            # 2^31 records where an f32 count goes wrong past 2^24
            pairs = [m.batch_result(out, y) for m in v_methods]
            vs = jnp.stack([jnp.asarray(v).astype(jnp.float32)
                            for v, _ in pairs])
            cs = jnp.stack([jnp.asarray(c).astype(jnp.int32)
                            for _, c in pairs])
            return av + vs, ac + cs

        scorer = tracked_jit(scorer_fn, site="eval.scorer",
                             donate_argnums=(4,))
    acc = None
    n_batches = 0
    for item in batches:
        t_batch = time.perf_counter()
        n_batches += 1
        batch = _as_minibatch(item)
        n = batch.size()
        data = jnp.asarray(batch.data)
        if full_bs is None:
            full_bs = n
        labels = jnp.asarray(batch.labels)
        if fast_ok and sliceable and n == full_bs:
            if cache is not None and not scorer_cached:
                cache.clear()  # fwd/methods changed: old entry is stale
                # graftlint: ignore[JG013] -- one-entry cache: cleared immediately above, so at most one program is ever retained
                cache[cache_key] = scorer
                scorer_cached = True
            if acc is None:
                acc = (jnp.zeros((len(v_methods),), jnp.float32),
                       jnp.zeros((len(v_methods),), jnp.int32))
            acc = scorer(params, buffers, data, labels, acc)
            count += n
            tm.eval_batch_seconds.observe(time.perf_counter() - t_batch)
            continue
        if n < full_bs and sliceable:
            pad = jnp.zeros((full_bs - n, *data.shape[1:]), data.dtype)
            out = fwd(params, buffers, jnp.concatenate([data, pad]))[:n]
        else:  # first batch, or structured output needing the exact shape
            out = fwd(params, buffers, data)
            if sliceable is None:
                sliceable = isinstance(out, jax.Array)
        for i, m in enumerate(v_methods):
            r = m.apply(out, labels)
            results[i] = r if results[i] is None else results[i] + r
        count += n
        tm.eval_batch_seconds.observe(time.perf_counter() - t_batch)
    tm.eval_batches_total.inc(n_batches)
    tm.eval_records_total.inc(count)
    if acc is not None:
        vals = np.asarray(acc[0])  # the ONE device->host sync
        counts = np.asarray(acc[1])
        for i, m in enumerate(v_methods):
            r = m.to_result(float(vals[i]), int(counts[i]))
            results[i] = r if results[i] is None else results[i] + r
    return results, count


class Evaluator:
    """reference ``optim/Evaluator.scala``."""

    def __init__(self, model: Module, batch_size: int = 128):
        self.model = model
        self.batch_size = batch_size
        self._eval_cache = {}  # scorer jit, traced once per (fwd, methods)

    def _as_batches(self, dataset):
        if isinstance(dataset, AbstractDataSet):
            return dataset.data(train=False)
        # raw list of Samples: batch them (reference uses SampleToBatch(4/p))
        ds = LocalDataSet(dataset) >> SampleToBatch(self.batch_size,
                                                    drop_remainder=False)
        return ds.data(train=False)

    def _fwd(self):
        # cached: repeated .test() calls (an eval loop) must not retrace
        if getattr(self, "_fwd_jit", None) is None:
            model = self.model

            def fwd(p, b, x):
                out, _ = functional_apply(model, p, b, x, training=False)
                return out

            self._fwd_jit = tracked_jit(fwd, site="eval.forward")
        return self._fwd_jit

    def test(self, dataset, v_methods: Sequence[ValidationMethod]
             ) -> List[Tuple[ValidationResult, ValidationMethod]]:
        params, buffers = self.model.functional_state()
        results, _ = evaluate_batches(self._fwd(), params, buffers,
                                      self._as_batches(dataset), v_methods,
                                      cache=self._eval_cache)
        return [(r, m) for r, m in zip(results, v_methods)]


class Predictor:
    """reference ``optim/Predictor.scala``."""

    def __init__(self, model: Module, batch_size: int = 128):
        self.model = model
        self.batch_size = batch_size

    def predict(self, dataset) -> List:
        ev = Evaluator(self.model, self.batch_size)
        fwd = ev._fwd()
        params, buffers = self.model.functional_state()
        outs = []
        for item in ev._as_batches(dataset):
            batch = _as_minibatch(item)
            outs.append(fwd(params, buffers, jnp.asarray(batch.data)))
        return outs

    def predict_class(self, dataset) -> List:
        return [jnp.argmax(o, axis=-1) + 1 for o in self.predict(dataset)]
