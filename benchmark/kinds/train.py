"""The training kind: ``build -> Optimizer(...).optimize()`` on the cell's
devices, wired as ``apps/perf.py`` and ``chip_smoke.py`` wire it, stopped by
a wall-clock trigger of the benchmark's own.

One ``optimize()`` call holds warm-up and the measured window. Completion
times are the loop's own per-iteration line (``Throughput is N
records/second``), which it logs right after the device->host fetch of that
iteration's loss. The window opens AT a completion and its length is taken
to the last completion at or before ``--seconds`` later, so the rate is
whole iterations over exactly the time they took.
"""

from __future__ import annotations

import logging
import re
import time

import numpy as np

from benchmark import harness, hlo_collectives

_LINE = re.compile(r"\[Iteration (\d+)\].*Trained (\d+) records.*"
                   r"Loss is (-?\d+\.\d+|nan|inf)")


class _Tap(logging.Handler):
    """Timestamps each per-iteration line as it is logged."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.done = []      # (perf_counter, iteration, records, loss)

    def emit(self, record):
        m = _LINE.search(record.getMessage())
        if m:
            self.done.append((time.perf_counter(), int(m.group(1)),
                              int(m.group(2)), float(m.group(3))))


class _Window:
    """The wall-clock trigger's state. ``__call__`` runs on the loop's own
    thread at every iteration boundary."""

    def __init__(self, tap, seconds, warmup, trace, devices):
        self.tap, self.seconds, self.warmup = tap, seconds, warmup
        self.trace, self.devices = trace, devices
        self.t_open = self.t_deadline = None
        self.before = self.after = None
        self.live = []      # bytes in use at the window's ends

    def __call__(self, state):
        if self.t_open is None:
            if len(self.tap.done) < self.warmup:
                return False
            self.t_open = self.tap.done[-1][0]      # AT a completion
            self.t_deadline = self.t_open + self.seconds
            self.before = harness.counters()
            self.live.append(harness.live_bytes(self.devices))
            return False
        now = time.perf_counter()
        if (self.trace is not None and not self.trace.started
                and now >= self.trace.starts_at(self.t_deadline,
                                                self.seconds)):
            self.trace.start()
        if now >= self.t_deadline:
            self.after = harness.counters()
            self.live.append(harness.live_bytes(self.devices))
            if self.trace is not None and self.trace.started:
                self.trace.stop()       # the loop's last flush follows
            return True
        return False


def _optim_method(spec):
    from bigdl_tpu import optim
    kind = spec["method"]
    if kind == "sgd":
        return optim.SGD(learningrate=spec["learningrate"],
                         momentum=spec.get("momentum", 0.0))
    if kind == "adamw":
        return optim.AdamW(learningrate=spec["learningrate"],
                           weightdecay=spec.get("weightdecay", 0.01))
    raise harness.BenchFailure(f"unknown optim method {kind!r}")


def _policy(name):
    from bigdl_tpu.ops.precision import DtypePolicy
    return DtypePolicy.bf16() if name == "bf16" else DtypePolicy()


def system_loss_and_grad_norm(model, criterion, policy, data, labels):
    """The program's own training loss closure (precision cast ->
    functional forward -> criterion) and the global L2 norm of its
    gradient, on one batch."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.optim.optimizer import make_training_loss_fn

    def f(params, buffers, data, labels):
        loss_fn = make_training_loss_fn(model, criterion, policy, (), False,
                                        buffers, jax.random.PRNGKey(0),
                                        data, labels)
        grads, (_, loss) = jax.grad(loss_fn, has_aux=True)(params)
        sq = sum(jnp.sum(g.astype(jnp.float32) ** 2)
                 for g in jax.tree_util.tree_leaves(grads))
        return loss, jnp.sqrt(sq)

    loss, gn = jax.jit(f)(model.parameter_tree(), model.buffer_tree(),
                          jnp.asarray(data), jnp.asarray(labels))
    return float(loss), float(gn)


def reference_check(builder, model, criterion, policy, cfg, cell, seed,
                    phases):
    """System against the plain float32 reference on one small seeded
    batch at the configuration's widths. The tolerances are the cell's:
    bf16 compute against float32 'highest' (a relative 2^-8 a rounding,
    accumulated over the depth), tight enough that dropping a term of the
    mathematics would fail."""
    tol = cell["reference"]
    data, labels = builder.reference_batch(cfg, cell, seed)
    cast = cell.get("cast_dtype")
    sys_data = data.astype(cast) if cast else data
    t = time.perf_counter()
    s_loss, s_gn = system_loss_and_grad_norm(model, criterion, policy,
                                             sys_data, labels)
    phases["reference_system_s"] = time.perf_counter() - t
    t = time.perf_counter()
    r_loss, r_gn = builder.reference_loss_and_grad_norm(model, cfg, data,
                                                        labels)
    phases["reference_plain_s"] = time.perf_counter() - t
    ok = (np.isfinite([s_loss, s_gn, r_loss, r_gn]).all()
          and abs(s_loss - r_loss) <= tol["loss_rtol"] * abs(r_loss)
          and abs(s_gn - r_gn) <= tol["grad_norm_rtol"] * abs(r_gn))
    return {"ok": bool(ok), "system_loss": s_loss, "reference_loss": r_loss,
            "system_grad_norm": s_gn, "reference_grad_norm": r_gn}


def run(ctx):
    import jax
    from bigdl_tpu.dataset import DeviceCachedDataSet
    from bigdl_tpu.dataset.base import DataSet
    from bigdl_tpu.optim import Optimizer, Trigger

    cell, cfg, seed = ctx["cell"], ctx["config"], ctx["seed"]
    devices = ctx["devices"]
    distributed = cell["chips"] > 1
    phases = ctx["phases"]
    builder = harness.load_builder(cfg["family"])

    t = time.perf_counter()
    model = builder.build(cfg, seed)
    criterion = builder.criterion(cfg)
    samples = builder.train_samples(cfg, cell, seed)
    policy = _policy(cell["precision"])
    phases["build_s"] = time.perf_counter() - t

    compiles0 = harness.counters()
    ref = reference_check(builder, model, criterion, policy, cfg, cell, seed,
                          phases)

    ds = DeviceCachedDataSet(
        DataSet.array(samples, distributed=distributed),
        batch_size=cell["batch_size"], cast_dtype=cell.get("cast_dtype"))
    opt = Optimizer(model, ds, criterion)
    want = "DistriOptimizer" if distributed else "LocalOptimizer"
    if type(opt).__name__ != want:
        raise harness.BenchFailure(f"the facade built {type(opt).__name__}")
    opt.set_optim_method(_optim_method(cell["optim"]))
    opt.set_precision(policy)
    if cell.get("clip_l2"):
        opt.set_gradient_clipping_by_l2_norm(cell["clip_l2"])

    tap = _Tap()
    trace = harness.TracedSlice(cell["name"]) if ctx["trace"] else None
    window = _Window(tap, ctx["seconds"], cell["warmup_iterations"], trace,
                     devices)
    opt.set_end_when(Trigger(window, "benchmarkWallClock"))

    log = logging.getLogger("bigdl_tpu.optim")
    level = log.level
    log.addHandler(tap)
    log.setLevel(logging.INFO)
    t_fit = time.perf_counter()
    try:
        opt.optimize()
    finally:
        log.removeHandler(tap)
        log.setLevel(level)
    if window.t_open is None or window.after is None:
        raise harness.BenchFailure("the window never opened")
    phases["first_step_s"] = tap.done[0][0] - t_fit  # data, compile, step 1
    phases["warmup_s"] = window.t_open - tap.done[0][0]
    ctx["t_window_open"] = window.t_open

    inside = [d for d in tap.done
              if window.t_open < d[0] <= window.t_deadline]
    if len(inside) < 2:
        raise harness.BenchFailure("fewer than two iterations in the window")
    span_s = inside[-1][0] - window.t_open
    records = sum(d[2] for d in inside)
    losses = [d[3] for d in tap.done]

    # ---- checks
    checks = {"reference": ref}
    by_site = {k: window.after[k] - compiles0.get(k, 0.0)
               for k in window.after if k.startswith("bigdl_compiles_total")}
    checks["compiles_by_site"] = by_site
    one_step_compile = by_site.get(
        "bigdl_compiles_total{site=train.step}", 0) == 1
    in_window = harness.counter_delta(window.before, window.after,
                                      "bigdl_compiles_total") or 0.0
    loss_ok = bool(np.isfinite(losses).all() and losses[-1] < losses[0])
    n_dev = len(devices)
    placed = all(len(leaf.devices()) == n_dev and
                 {d.platform for d in leaf.devices()} == {devices[0].platform}
                 for leaf in jax.tree_util.tree_leaves(
                     model.parameter_tree()))
    texts = getattr(opt.step_fn, "tracked", opt.step_fn).compiled_texts()
    hlo = texts[0] if len(texts) == 1 else ""
    coll = hlo_collectives.collectives(hlo)
    mesh_ok = True
    if distributed:
        ar = coll.get("all-reduce")
        used = [int((d.memory_stats() or {}).get("bytes_in_use", 1))
                for d in devices]
        mesh_ok = bool(ar and ar["count"] > 0 and ar["groups"] == [n_dev]
                       and all(u > 0 for u in used))
        checks["all_reduce"] = ar
        checks["bytes_in_use"] = used
    checks.update(loss_first=losses[0], loss_last=losses[-1],
                  loss_ok=loss_ok, one_step_compile=one_step_compile,
                  compiles_in_window=in_window, placed=placed,
                  mesh_ok=mesh_ok)
    correct = bool(ref["ok"] and loss_ok and one_step_compile
                   and in_window == 0 and placed and mesh_ok)

    ctx.update(hlo=hlo, collectives=coll, counters_before=window.before,
               counters_after=window.after, live_bytes=max(window.live),
               device_trace=trace,
               span_events=trace.events if trace is not None else [],
               steps_in_window=len(inside),
               step_seconds=span_s / len(inside),
               span_names=("train.sync", "train.dispatch"))
    return {"correct": correct, "attempted": len(inside), "failed": 0,
            "checks": checks,
            "values": {"train_records_per_s": records / span_s}}
