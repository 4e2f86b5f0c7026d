"""Optimizer facade + single-chip training loop
(reference ``optim/Optimizer.scala:42`` factory at ``:278-333``,
``optim/LocalOptimizer.scala:39``).

Where the reference's LocalOptimizer clones one model replica per core and
hand-reduces their gradients (``LocalOptimizer.scala:52-141``), the TPU loop
is **one jitted step**: forward + backward (autodiff) + optimizer update fused
into a single XLA program, donated buffers, no host round-trips except the
scalar loss. Intra-chip parallelism is XLA's job, not a thread pool's.

The facade keeps the reference's builder surface: ``set_validation``,
``set_checkpoint``, ``set_train_summary``, ``set_state``, ``set_optim_method``,
``set_end_when``, ``optimize()``.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.dataset.base import (AbstractDataSet, DistributedDataSet,
                                    MiniBatch, SampleToBatch)
from bigdl_tpu.nn.criterion import Criterion
from bigdl_tpu.nn.module import Module, functional_apply
from bigdl_tpu.optim.methods import OptimMethod, SGD
from bigdl_tpu.optim.metrics import Metrics
from bigdl_tpu.optim.triggers import Trigger
from bigdl_tpu.optim.validation import ValidationMethod
from bigdl_tpu.resilience.preemption import (PreemptionHandler,
                                             TrainingPreempted)
from bigdl_tpu.telemetry import get_registry, instruments, span, tracing
from bigdl_tpu.telemetry import profiling
from bigdl_tpu.telemetry.profiling import sample_device_memory, tracked_jit
from bigdl_tpu.utils import file_io
from bigdl_tpu.utils.rng import RandomGenerator
from bigdl_tpu.utils.table import Table, T

logger = logging.getLogger("bigdl_tpu.optim")


def _regularizer_pairs(model: Module):
    """[(path_tuple, Regularizer)] for params with an attached regularizer."""
    import jax.tree_util as jtu
    reg_leaves, reg_treedef = jtu.tree_flatten(
        model.regularizer_tree(), is_leaf=lambda x: x is None or hasattr(x, "loss"))
    param_paths = [p for p, _ in jtu.tree_flatten_with_path(model.parameter_tree())[0]]
    out = []
    for path, reg in zip(param_paths, reg_leaves):
        if reg is not None:
            out.append((path, reg))
    return out


def _reg_loss(params, reg_pairs):
    import jax.tree_util as jtu
    if not reg_pairs:
        return 0.0
    by_path = {tuple(str(k) for k in p): r for p, r in reg_pairs}
    total = 0.0
    for path, leaf in jtu.tree_flatten_with_path(params)[0]:
        key = tuple(str(k) for k in path)
        if key in by_path:
            total = total + by_path[key].loss(leaf)
    return total


def make_grad_clipper(clip):
    """Gradient-clip transform from an Optimizer's ``_grad_clip`` setting —
    a dict with optional ``"constant": (lo, hi)`` (elementwise clamp) and
    ``"l2": max_norm`` (global-L2 rescale) entries. Both may be active at
    once (reference: independent parameter processors); the clamp applies
    FIRST, then the norm bound, so the L2 guarantee always holds on the
    final gradient. ``None``/empty: identity. For the ZeRO-1 sharded
    plane, pass ``axis_name`` so the squared norm reduces across the
    slice shards (each device holds 1/P of the flat gradient)."""
    if not clip:
        return lambda g, axis_name=None, valid_mask=None: g
    const = clip.get("constant")
    max_norm = clip.get("l2")

    def apply(g, axis_name=None, valid_mask=None):
        if const is not None:
            lo, hi = const
            g = jax.tree_util.tree_map(lambda x: jnp.clip(x, lo, hi), g)
        if valid_mask is not None:
            # flat-vector padding lanes (ZeRO-1): a clamp range excluding 0
            # would lift the pad zeros and pollute the global norm below
            g = jax.tree_util.tree_map(lambda x: x * valid_mask, g)
        if max_norm is not None:
            leaves = jax.tree_util.tree_leaves(g)
            gn_sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves)
            if axis_name is not None:
                gn_sq = jax.lax.psum(gn_sq, axis_name)
            scale = jnp.minimum(1.0, max_norm
                                * jax.lax.rsqrt(jnp.maximum(gn_sq, 1e-24)))
            g = jax.tree_util.tree_map(
                lambda x: (x * scale).astype(x.dtype), g)
        return g

    return apply


def make_training_loss_fn(model, criterion, policy, reg_pairs, remat,
                          buffers, rng, data, labels):
    """The ONE training loss closure shared by every step builder (local,
    distributed allreduce, ZeRO-1 sharded): precision cast -> functional
    forward (optionally rematerialized via ``jax.checkpoint``) -> criterion
    + regularizer, returning ``(loss, (new_buffers, raw_loss))``. The cast
    and the loss run under the scopes ``param_cast`` and ``criterion``: two
    of the step's own stages in its partition
    (``telemetry/step_partition.py``; ``clipped_update`` has the others)."""
    def forward(p, data):
        from bigdl_tpu.ops.precision import cast_tree
        with jax.named_scope("param_cast"):
            p_c = policy.cast_params_for_compute(p)
        out, new_buf = functional_apply(model, p_c, buffers, data,
                                        training=True, rng=rng)
        return out, cast_tree(new_buf, jnp.float32)

    fwd = jax.checkpoint(forward) if remat else forward

    def loss_fn(p):
        out, new_buf = fwd(p, data)
        with jax.named_scope("criterion"):
            loss = criterion.apply(out, labels).astype(jnp.float32)
            return loss + _reg_loss(p, reg_pairs), (new_buf, loss)

    return loss_fn


def clipped_update(optim, clip, grads, opt_state, params, **clip_args):
    """``optim.update`` on the clipped gradient, as every step builder ends:
    the clipper (``make_grad_clipper``; ``clip_args`` are its ZeRO-1
    arguments) under the scope ``grad_clip``, the update under
    ``optim_update``."""
    with jax.named_scope("grad_clip"):
        grads = clip(grads, **clip_args)
    with jax.named_scope("optim_update"):
        return optim.update(grads, opt_state, params)


class Optimizer:
    """Facade/factory (reference ``Optimizer.scala:278-333``): constructing
    ``Optimizer(model, dataset, criterion)`` yields a LocalOptimizer or — for
    a DistributedDataSet — a DistriOptimizer.

    Examples::

        >>> import numpy as np
        >>> from bigdl_tpu import nn
        >>> from bigdl_tpu.dataset.base import DataSet, Sample, SampleToBatch
        >>> from bigdl_tpu.optim import SGD, Trigger
        >>> rng = np.random.RandomState(0)
        >>> ds = (DataSet.array([Sample(rng.randn(4).astype(np.float32),
        ...                             float(i % 2 + 1))
        ...                      for i in range(32)]) >> SampleToBatch(16))
        >>> model = (nn.Sequential().add(nn.Linear(4, 2))
        ...          .add(nn.LogSoftMax()))
        >>> opt = (Optimizer(model, ds, nn.ClassNLLCriterion())
        ...        .set_optim_method(SGD(learningrate=0.1))
        ...        .set_end_when(Trigger.max_iteration(2)))
        >>> type(opt).__name__
        'LocalOptimizer'
        >>> trained = opt.optimize()
        >>> trained is model
        True
    """

    def __new__(cls, model: Module = None, dataset: AbstractDataSet = None,
                criterion: Criterion = None, **kwargs):
        if (cls is Optimizer and dataset is not None
                and dataset.is_distributed()):
            from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
            return super().__new__(DistriOptimizer)
        if cls is Optimizer:
            return super().__new__(LocalOptimizer)
        return super().__new__(cls)

    def __init__(self, model: Module, dataset: AbstractDataSet,
                 criterion: Criterion, **kwargs):
        self.model = model
        self.dataset = dataset
        self.criterion = criterion
        self.optim_method: OptimMethod = SGD()
        self.end_when: Trigger = Trigger.max_epoch(10)
        self.validation_trigger: Optional[Trigger] = None
        self.validation_dataset: Optional[AbstractDataSet] = None
        self.validation_methods: Optional[List[ValidationMethod]] = None
        self.checkpoint_path: Optional[str] = None
        self.checkpoint_trigger: Optional[Trigger] = None
        self.is_overwrite = False
        self.train_summary = None
        self.validation_summary = None
        self.state: Table = T()
        self.metrics = Metrics()
        self._resume_from: Optional[Tuple[str, str]] = None
        self._profile: Optional[Tuple[str, int, int]] = None
        self._remat = False
        self._grad_clip = {}
        self._eval_cache = {}  # validation scorer jit, traced once
        #: the tracked ``train.step`` of the latest ``optimize()``, kept for
        #: inspection after the run (compile events, program text)
        self.step_fn = None
        # resilience (bigdl_tpu/resilience, docs/RESILIENCE.md)
        self._preemption: Optional[PreemptionHandler] = None
        self._auto_resume = False
        self._chaos: List = []
        self._loop_cursor: Optional[Dict] = None  # data-iterator position
        self._loop_rng = None                     # the loop's key stream
        from bigdl_tpu.ops.precision import DtypePolicy
        self.precision = DtypePolicy.fp32()

    # ---------------------------------------------------------------- builder
    def set_validation(self, trigger: Trigger, dataset: AbstractDataSet,
                       v_methods: Sequence[ValidationMethod]) -> "Optimizer":
        self.validation_trigger = trigger
        self.validation_dataset = dataset
        self.validation_methods = list(v_methods)
        return self

    def set_checkpoint(self, path: str, trigger: Trigger,
                       sharded: bool = False) -> "Optimizer":
        """``sharded=True``: per-process shard files, no driver gather
        (``utils/sharded_checkpoint.py``) — replaces the reference's
        reassemble-on-driver snapshot (``DistriOptimizer.scala:378-400``)
        for multi-host/FSDP states; restore reshards onto the resuming
        run's mesh. Local filesystem paths only."""
        self.checkpoint_path = path
        self.checkpoint_trigger = trigger
        self._ckpt_sharded = sharded
        return self

    def overwrite_checkpoint(self) -> "Optimizer":
        self.is_overwrite = True
        return self

    def set_train_summary(self, summary) -> "Optimizer":
        self.train_summary = summary
        return self

    def set_validation_summary(self, summary) -> "Optimizer":
        self.validation_summary = summary
        return self

    def set_state(self, state: Table) -> "Optimizer":
        self.state = state
        return self

    def set_optim_method(self, method: OptimMethod) -> "Optimizer":
        if not getattr(method, "supports_minibatch", True):
            # Fail at configuration time, not at step time (reference LBFGS
            # is likewise a full-batch optimize(feval, x) driver,
            # ``optim/LBFGS.scala:38``).
            raise ValueError(
                f"{type(method).__name__} is a full-batch method and cannot "
                "drive the minibatch training loop; call "
                "method.optimize(feval, x) directly instead")
        self.optim_method = method
        return self

    def set_end_when(self, end_when: Trigger) -> "Optimizer":
        self.end_when = end_when
        return self

    def set_remat(self, enabled=True) -> "Optimizer":
        """Rematerialize the forward in the backward pass (``jax.checkpoint``).

        ``True``: full remat — activation memory drops to O(1) forwards at
        ~1.3x FLOPs, the standard TPU recipe when a model does not fit HBM.

        ``"block"``: per-transformer-block checkpointing — every
        ``TransformerEncoder`` or ``HybridDecoder`` in the model recomputes
        inside each block during the backward. A ``TransformerEncoder``
        keeps only block-boundary activations. A ``HybridDecoder`` also
        keeps what ``ops.remat.BLOCK_SAVED_NAMES`` lists, one list for
        every caller: flash attention's ``o`` and ``lse``, the q, k, v,
        gate and out projections' outputs, a dense gated MLP's three
        products, a Mamba-2 in-projection's output, a held-expert layer's
        routing tables, routed output and its shared expert's float32
        first products (17.5-30.8 KB a token an attention block, 28.7 KB a
        dense block, 20.6 KB a Mamba-2 block, 12.4-20.3 KB an expert block
        at the benchmark's widths in bf16), so the backward's second
        forward is norms, rotation, gates, the convolution, the scan, the
        router's product and the shared expert's second product. THE
        policy for billion-param LMs (full remat saves nothing there: one
        outer checkpoint re-materialises all intermediates in its replay).

        Off by default (compute-bound models should keep activations)."""
        from bigdl_tpu.nn.attention import TransformerEncoder
        from bigdl_tpu.nn.hybrid import HybridDecoder
        encs = [m for m in self.model.modules()
                if isinstance(m, (TransformerEncoder, HybridDecoder))]
        for enc in encs:  # reset; "block" re-enables below
            enc.remat_blocks = False
        if isinstance(enabled, str):
            if enabled == "full":  # alias for True
                self._remat = True
            elif enabled == "block":
                if not encs:
                    raise ValueError("remat='block' needs a model with "
                                     "TransformerEncoder or HybridDecoder "
                                     "blocks")
                for enc in encs:
                    enc.remat_blocks = True
                self._remat = False  # per-block checkpoints, no outer wrap
            else:
                raise ValueError(f"unknown remat policy {enabled!r}; "
                                 "expected True/False, 'full' or 'block'")
        else:
            self._remat = bool(enabled)
        return self

    def set_gradient_clipping_by_l2_norm(self, clip_norm: float) -> "Optimizer":
        """Scale gradients so their GLOBAL L2 norm (over the whole parameter
        tree, and across data shards under DistriOptimizer) never exceeds
        ``clip_norm`` (reference ``Optimizer.setGradientClippingByl2Norm``).
        Applied inside the jitted step, between autodiff and the update."""
        if clip_norm <= 0:
            raise ValueError(f"clip_norm must be > 0, got {clip_norm}")
        self._grad_clip = {**self._grad_clip, "l2": float(clip_norm)}
        return self

    def set_constant_gradient_clipping(self, min_value: float,
                                       max_value: float) -> "Optimizer":
        """Clamp every gradient element into [min_value, max_value]
        (reference ``Optimizer.setConstantGradientClipping``)."""
        if not min_value < max_value:
            raise ValueError(f"need min_value < max_value, got "
                             f"[{min_value}, {max_value}]")
        self._grad_clip = {**self._grad_clip,
                           "constant": (float(min_value), float(max_value))}
        return self

    def disable_gradient_clipping(self) -> "Optimizer":
        """reference ``Optimizer.disableGradientClipping`` (clears both)."""
        self._grad_clip = {}
        return self

    def set_precision(self, policy) -> "Optimizer":
        """'bf16' / 'fp32' or a DtypePolicy: bf16 compute with fp32 master
        params (the MXU-native recipe; see ``ops/precision.py``)."""
        from bigdl_tpu.ops.precision import DtypePolicy
        if isinstance(policy, str):
            try:
                policy = {"bf16": DtypePolicy.bf16,
                          "fp32": DtypePolicy.fp32}[policy]()
            except KeyError:
                raise ValueError(
                    f"unknown precision {policy!r}; use 'bf16', 'fp32', "
                    f"or a DtypePolicy") from None
        self.precision = policy
        return self

    def resume(self, model_path: str, state_path: str) -> "Optimizer":
        """Continue from snapshot files (reference examples' --model/--state)."""
        self._resume_from = (model_path, state_path)
        return self

    def auto_resume(self, enabled: bool = True) -> "Optimizer":
        """On ``optimize()``, discover the newest COMPLETE snapshot under
        ``checkpoint_path`` (partial writes rejected) and continue from it
        — the relaunch half of preemption survival. A RESUME marker
        (written by every checkpoint save) restores the data-iterator
        cursor and the exact per-step key stream, so a mid-epoch restart
        is bit-exact; the snapshot reshards onto THIS run's mesh even if
        the process count changed (elastic resume, docs/RESILIENCE.md)."""
        self._auto_resume = bool(enabled)
        return self

    def set_preemption_handler(self,
                               handler: Optional[PreemptionHandler] = None
                               ) -> "Optimizer":
        """Install SIGTERM (by default) preemption hooks for the duration
        of ``optimize()``: on a notice, the loop finishes the step in
        flight, writes one final snapshot + RESUME marker under
        ``checkpoint_path`` and raises ``TrainingPreempted`` — at most one
        step of work is lost (single-host; multi-host runs agree on the
        snapshot step via a periodic flag all-gather, so loss is bounded
        by the ``BIGDL_PREEMPT_SYNC_EVERY`` cadence, default 10 steps —
        set 1 for strict one-step loss at a per-step collective cost)."""
        self._preemption = handler if handler is not None \
            else PreemptionHandler()
        return self

    def set_chaos(self, injectors: Sequence) -> "Optimizer":
        """Deterministic fault injectors probed at every step boundary
        (``bigdl_tpu.resilience.chaos``); the env spec ``BIGDL_CHAOS``
        (e.g. ``kill@5``) adds to these at ``optimize()`` time."""
        self._chaos = list(injectors)
        return self

    def set_profiling(self, log_dir: str, start_iteration: int = 5,
                      n_iterations: int = 5) -> "Optimizer":
        """Capture a ``jax.profiler`` trace of iterations
        [start_iteration, start_iteration + n_iterations) under
        ``log_dir``, and reduce it to the reference's ``getTimes``
        (``AbstractModule.scala:134-145``) under jit: when the profile
        stops, ``<log_dir>/step_partition.json`` and a log line hold the
        step's device time by layer and pass (forward, recompute, backward,
        update), in ms a step and % of it. A layer is a scope of
        ``telemetry/catalogue.SCOPE_SPECS`` (every module's forward runs
        under ``jax.named_scope(module.name)``, the step's stages and the
        mixers' parts under leaf scopes of their own);
        ``telemetry/step_partition.py`` says how an operation finds its
        cell. The dump itself opens in TensorBoard's profile plugin or
        Perfetto. The span tracer (``telemetry/tracing.py``) is on for the
        profiled window, so the loop's ``train.*`` spans are in the same
        trace, on the thread that enqueues the step."""
        self._profile = (log_dir, int(start_iteration), int(n_iterations))
        return self

    def optimize(self) -> Module:
        raise NotImplementedError

    def _start_profile(self, log_dir: str) -> None:
        jax.profiler.start_trace(log_dir)
        self._profiling_active = True
        # the spans go into the profile; a tracer somebody else enabled
        # is left as it is when the profile stops
        self._profile_owns_tracer = not tracing.is_enabled()
        if self._profile_owns_tracer:
            tracing.enable()

    def _stop_profile(self) -> None:
        jax.profiler.stop_trace()
        self._profiling_active = False
        if self._profile_owns_tracer:
            tracing.disable()
        self._write_step_partition(self._profile[0])

    def _write_step_partition(self, log_dir: str) -> None:
        """The profile just written, reduced to the step's device time by
        layer and pass (``telemetry/step_partition.py``): logged, and kept
        as ``<log_dir>/step_partition.json``. Once, after ``stop_trace``;
        a profile it cannot read costs a warning, never the run."""
        from bigdl_tpu.telemetry import step_partition
        step = getattr(self.step_fn, "tracked", self.step_fn)
        t0 = time.perf_counter()
        try:
            texts = step.compiled_texts()
            report = step_partition.write_report(log_dir, texts[-1]) \
                if texts else None
            if texts and "optim_update" not in texts[-1]:
                logger.warning(
                    "[Profiler] the step's executable carries none of this "
                    "checkout's scopes: a compile cache filled by an older "
                    "checkout served it; clear it for a table by layer")
        except Exception as e:      # noqa: BLE001 - a reader's fault
            logger.warning("[Profiler] no step partition: %r", e)
            return
        if report is None:
            logger.info("[Profiler] the profile holds no run of the step "
                        "program: no step partition")
        else:
            logger.info("[Profiler] %s\n(%s, reduced in %.2f s)",
                        step_partition.format_table(report),
                        os.path.join(log_dir, "step_partition.json"),
                        time.perf_counter() - t0)

    def _telemetry_mode(self) -> str:
        """Label value for the ``bigdl_train_*`` metric families
        (docs/OBSERVABILITY.md); DistriOptimizer overrides with its mesh
        sync mode so local and distributed step breakdowns stay separate
        series in one scrape."""
        return "local"

    def _mesh_descriptor(self) -> Dict[str, Any]:
        """The topology recorded in RESUME markers — what elastic-resume
        detection compares against the restarting run's; DistriOptimizer
        overrides with its mesh shape + sync mode."""
        return {"process_count": int(jax.process_count()),
                "device_count": int(jax.device_count()),
                "mesh_shape": None, "sync_mode": "local"}

    def _train_instruments(self):
        """The mode-labeled training metric children (step-time breakdown,
        throughput, compile counter) as a namespace; resolved once per
        optimizer and cached (label resolution costs a schema check per
        child — not something _validate should re-pay every trigger)."""
        cached = getattr(self, "_tm_cache", None)
        if cached is not None:
            return cached
        from types import SimpleNamespace
        tm = instruments(get_registry())
        mode = self._telemetry_mode()
        cached = SimpleNamespace(
            step=tm.train_step_seconds.labels(mode=mode),
            data_wait=tm.train_data_wait_seconds.labels(mode=mode),
            dispatch=tm.train_dispatch_seconds.labels(mode=mode),
            sync=tm.train_sync_seconds.labels(mode=mode),
            steps=tm.train_steps_total.labels(mode=mode),
            records=tm.train_records_total.labels(mode=mode),
            rps=tm.train_records_per_second.labels(mode=mode),
            mfu=tm.train_mfu.labels(mode=mode),
            validation=tm.train_validation_seconds.labels(mode=mode))
        self._tm_cache = cached
        return cached

    # ------------------------------------------------------------ checkpoint
    def _save_checkpoint(self, params, buffers, opt_state, driver_state) -> None:
        if self.checkpoint_path is None:
            return
        tag = "" if self.is_overwrite else f".{int(driver_state['neval'])}"
        if getattr(self, "_ckpt_sharded", False):
            import json as _json
            from bigdl_tpu.utils import sharded_checkpoint as sckpt
            sckpt.save_sharded(
                file_io.join(self.checkpoint_path, f"model{tag}"),
                {"params": params, "buffers": buffers})
            state_dir = file_io.join(self.checkpoint_path, f"state{tag}")
            sckpt.save_sharded(state_dir, {"optim": opt_state})
            if jax.process_index() == 0:
                driver = {k: (v.item() if hasattr(v, "item") else v)
                          for k, v in dict(driver_state).items()}
                with open(os.path.join(state_dir, "driver.json"), "w") as f:
                    _json.dump(driver, f)
        else:
            file_io.save({"params": params, "buffers": buffers},
                         file_io.join(self.checkpoint_path, f"model{tag}"))
            file_io.save({"optim": opt_state, "driver": dict(driver_state)},
                         file_io.join(self.checkpoint_path, f"state{tag}"))
        self._write_resume_marker(driver_state, tag)
        logger.info("[Checkpoint] saved model%s to %s", tag, self.checkpoint_path)

    def _write_resume_marker(self, driver_state, tag: str) -> None:
        """RESUME marker beside the state snapshot (process 0; written
        LAST): step/epoch, the loop's exact PRNG key state, the data
        cursor and this run's mesh shape — what makes the snapshot
        mid-epoch bit-exact and elastically resumable. No-op outside a
        live training loop (no cursor yet)."""
        if self._loop_cursor is None or self._loop_rng is None:
            return
        if jax.process_index() != 0:
            return
        if "://" in self.checkpoint_path:
            return  # markers are a local-fs refinement; scheme'd snapshots
            # resume epoch-granular exactly as before
        from bigdl_tpu.resilience import coordinator
        coordinator.write_marker(
            file_io.join(self.checkpoint_path, f"state{tag}"),
            step=int(driver_state["neval"]),
            epoch=int(driver_state["epoch"]),
            rng_key_data=self._loop_rng.get_key_state(),
            rng_seed=self._loop_rng.get_seed(),
            epoch_batches=int(self._loop_cursor["epoch_batches"]),
            epoch_records=int(self._loop_cursor["epoch_records"]),
            mesh=self._mesh_descriptor(),
            cursor_epoch=int(self._loop_cursor["epoch"]))

    def _resume_shardings(self, params_tpl, buffers_tpl):
        """Target shardings for a sharded-checkpoint resume: pytrees of
        Sharding (or None = host numpy) matching (params, buffers,
        opt_state). LocalOptimizer restores to host; DistriOptimizer
        overrides to reshard onto its mesh."""
        none_of = lambda tpl: jax.tree_util.tree_map(lambda _: None, tpl)
        state_tpl = jax.eval_shape(self.optim_method.init_state, params_tpl)
        return none_of(params_tpl), none_of(buffers_tpl), none_of(state_tpl)

    def _load_sharded_checkpoint(self, model_path, state_path):
        """(params, buffers, opt_state, driver) from per-shard files,
        resharded onto this run's placement — possibly a different mesh
        shape than the saving run's (``utils/sharded_checkpoint.py``)."""
        import json as _json
        from bigdl_tpu.utils import sharded_checkpoint as sckpt
        params_tpl = self.model.parameter_tree()
        buffers_tpl = self.model.buffer_tree()
        p_sh, b_sh, s_sh = self._resume_shardings(params_tpl, buffers_tpl)
        snap = sckpt.load_sharded(model_path,
                                  {"params": p_sh, "buffers": b_sh})
        st = sckpt.load_sharded(state_path, {"optim": s_sh})
        with open(os.path.join(state_path, "driver.json")) as f:
            driver = _json.load(f)
        return snap["params"], snap["buffers"], st["optim"], driver


class LocalOptimizer(Optimizer):
    """Single-chip training loop (reference ``optim/LocalOptimizer.scala:39``)."""

    # Subclass hooks (DistriOptimizer overrides for mesh placement/sharding).
    def _place_batch(self, batch: MiniBatch):
        return jnp.asarray(batch.data), jnp.asarray(batch.labels)

    def _init_opt_state(self, params):
        return self.optim_method.init_state(params)

    def _place_state(self, params, buffers, opt_state):
        """Device-placement hook: DistriOptimizer overrides to commit the
        training state onto the (possibly multi-host) mesh before jit."""
        return params, buffers, opt_state

    def _finalize_params(self, params):
        return params

    def _build_step(self) -> Callable:
        model, criterion, optim = self.model, self.criterion, self.optim_method
        reg_pairs = _regularizer_pairs(model)
        policy = self.precision
        remat = self._remat
        clip = make_grad_clipper(self._grad_clip)

        def step(params, buffers, opt_state, rng, data, labels):
            loss_fn = make_training_loss_fn(
                model, criterion, policy, reg_pairs, remat,
                buffers, rng, data, labels)
            grads, (new_buf, loss) = jax.grad(loss_fn, has_aux=True)(params)
            new_params, new_opt_state = clipped_update(
                optim, clip, grads, opt_state, params)
            return new_params, new_buf, new_opt_state, loss

        # compile flight recorder: counts/times every step compilation
        # and yields the program's cost analysis — the FLOPs numerator of
        # the live bigdl_train_mfu gauge (telemetry/profiling.py)
        return tracked_jit(step, site="train.step", donate_argnums=(0, 1, 2))

    def _build_forward(self) -> Callable:
        model = self.model

        def fwd(params, buffers, data):
            out, _ = functional_apply(model, params, buffers, data, training=False)
            return out

        return tracked_jit(fwd, site="train.forward")

    def optimize(self) -> Module:
        """Train with retry-from-checkpoint (reference
        ``DistriOptimizer.scala:728-796``): on a non-configuration failure,
        reload the newest COMPLETE snapshot under ``checkpoint_path``
        (partial writes rejected by the resilience coordinator) and retry,
        up to ``BIGDL_FAILURE_RETRY_TIMES`` (default 5) failures inside a
        sliding ``BIGDL_FAILURE_RETRY_INTERVAL``-second window (default
        120). ``TrainingPreempted`` is NOT retried — the host is going
        away; the snapshot it wrote is picked up by ``auto_resume()`` on
        relaunch."""
        from bigdl_tpu.resilience import chaos as chaos_mod
        from bigdl_tpu.resilience import coordinator
        retry_times = int(os.environ.get("BIGDL_FAILURE_RETRY_TIMES", "5"))
        retry_window = float(
            os.environ.get("BIGDL_FAILURE_RETRY_INTERVAL", "120"))
        failures: List[float] = []
        resume = self._resume_from
        if resume is None and self._auto_resume:
            point = coordinator.latest_resume_point(self.checkpoint_path)
            if point is not None:
                resume = point
                logger.info("[AutoResume] discovered snapshot %s",
                            point.model_path)
        self._chaos_live = list(self._chaos) + chaos_mod.from_env()
        handler = self._preemption
        if handler is not None:
            handler.install()
            drain = getattr(self.dataset, "drain", None)
            if callable(drain):
                # ingest-engine datasets: stop + join the reader/decode/
                # device-feed threads before the final snapshot's IO
                handler.add_drain_hook(drain)
        try:
            while True:
                try:
                    return self._run_training(resume)
                except (ValueError, TypeError, KeyboardInterrupt,
                        TrainingPreempted):
                    raise  # config errors ≙ IllegalArgument; preemption ≙
                    # the host is being reclaimed — don't spin on it
                except Exception as e:  # noqa: BLE001 - the retry boundary
                    now = time.time()
                    failures = [t for t in failures if now - t < retry_window]
                    failures.append(now)
                    latest = (coordinator.latest_resume_point(
                        self.checkpoint_path) if self.checkpoint_path
                        else None)
                    if len(failures) > retry_times or latest is None:
                        raise
                    # IN-PROCESS retry: the dataset's in-place shuffle
                    # order and the host RNG have already advanced past
                    # their fresh-process state, so the marker's shuffle
                    # replay + batch-cursor fast-forward would align to
                    # the wrong permutation (training some records twice,
                    # skipping others). Drop the marker — the epoch
                    # restarts from batch 0, the pre-resilience retry
                    # semantics. A fresh-process relaunch (auto_resume)
                    # keeps the marker and resumes bit-exact.
                    import dataclasses
                    resume = dataclasses.replace(latest, marker=None)
                    logger.warning(
                        "[Retry %d/%d] training failed (%s); restarting "
                        "from checkpoint %s", len(failures), retry_times, e,
                        latest.model_path)
        finally:
            self._close_data_iter()
            if handler is not None:
                handler.uninstall()

    def _latest_checkpoint(self) -> Optional[Tuple[str, str]]:
        """Newest COMPLETE (model, state) snapshot pair under
        ``checkpoint_path`` (reference ``getLatestFile``,
        ``DistriOptimizer.scala:808-825``; completeness validation in
        ``bigdl_tpu/resilience/coordinator.py``)."""
        from bigdl_tpu.resilience import coordinator
        point = coordinator.latest_resume_point(self.checkpoint_path)
        if point is None:
            return None
        return (point.model_path, point.state_path)

    def _run_training(self, resume) -> Module:
        model = self.model
        # Private copies: the jitted step donates its param/buffer inputs, and
        # donating the model's own arrays would delete buffers any other
        # reference (a cloned model, user code) still points at.
        driver_state = T(epoch=1, neval=1)
        driver_state.update(self.state)

        from bigdl_tpu.resilience import coordinator
        marker = None
        if resume:
            if isinstance(resume, coordinator.ResumePoint):
                model_path, state_path = resume.model_path, resume.state_path
                marker = resume.marker
            else:
                model_path, state_path = resume
                marker = coordinator.read_marker(state_path)
            from bigdl_tpu.utils import sharded_checkpoint as sckpt
            if sckpt.is_sharded_checkpoint(model_path):
                params, buffers, opt_state, driver = \
                    self._load_sharded_checkpoint(model_path, state_path)
                driver_state.update(driver)
            else:
                snap = file_io.load(model_path)
                params, buffers = snap["params"], snap["buffers"]
                st = file_io.load(state_path)
                opt_state = st["optim"]
                driver_state.update(st["driver"])
            elastic = coordinator.is_elastic(marker)
            instruments(get_registry()).resilience_resumes_total.labels(
                elastic="unknown" if elastic is None
                else ("true" if elastic else "false")).inc()
            if elastic:
                saved = (marker.get("mesh") or {})
                logger.info(
                    "[Resume] ELASTIC: snapshot saved by %s processes / %s "
                    "devices, resharding onto %d processes / %d devices",
                    saved.get("process_count"), saved.get("device_count"),
                    jax.process_count(), jax.device_count())
            logger.info("[Resume] from %s at epoch %s neval %s", model_path,
                        driver_state["epoch"], driver_state["neval"])
        else:
            params = jax.tree_util.tree_map(jnp.array, model.parameter_tree())
            buffers = jax.tree_util.tree_map(jnp.array, model.buffer_tree())
            opt_state = self._init_opt_state(params)
        params, buffers, opt_state = self._place_state(params, buffers,
                                                       opt_state)
        # The trainer works on its private copies; the model's own
        # parameter arrays would sit in HBM beside them for the whole run
        # (4 bytes a parameter, 2 GB for a 0.5B model). Keep them on the
        # host until the trained values are loaded back at the end. The
        # device arrays are not deleted, only let go of: one that a clone
        # or user code still points at lives on.
        model.load_parameter_tree(jax.tree_util.tree_map(
            np.asarray, model.parameter_tree()))
        try:
            return self._train_loop(params, buffers, opt_state,
                                    driver_state, marker)
        except BaseException:
            # left as it was found: device arrays (of the values it had)
            model.load_parameter_tree(jax.tree_util.tree_map(
                jnp.asarray, model.parameter_tree()))
            raise

    def _train_loop(self, params, buffers, opt_state, driver_state,
                    marker) -> Module:
        model = self.model
        step = self.step_fn = self._build_step()
        fwd = self._build_forward()
        uses_loss_any = (getattr(self.end_when, "uses_loss", False)
                         or getattr(self.validation_trigger, "uses_loss",
                                    False)
                         or getattr(self.checkpoint_trigger, "uses_loss",
                                    False))
        self._profiling_active = False
        rng = RandomGenerator.RNG()
        from bigdl_tpu.utils.engine import Engine
        n_proc = Engine.process_count()
        if n_proc > 1:
            # SPMD contract: replicated jit inputs (dropout keys) must be
            # identical on every process — sync the stream to process 0's.
            from jax.experimental import multihost_utils
            seed = int(multihost_utils.broadcast_one_to_all(
                np.asarray(rng.get_seed(), np.int64)))
            rng = RandomGenerator(seed)
        resume_cursor = None
        if marker is not None:
            # Bit-exact mid-epoch restart (docs/RESILIENCE.md): restore the
            # loop's exact key-stream position, replay the per-epoch
            # shuffles a fresh process has not performed (the composed
            # in-place permutation then matches the uninterrupted run —
            # provided the host RNG is consumed only by these shuffles),
            # and skip the batches the saved epoch already consumed.
            key_data = (marker.get("rng") or {}).get("key_data")
            if key_data:
                rng = RandomGenerator(int(marker["rng"]["seed"]))
                rng.set_key_state(key_data)
            for _ in range(int(driver_state["epoch"]) - 1):
                self.dataset.shuffle()
            resume_cursor = dict(marker.get("cursor") or {})
        self._loop_cursor = None  # set at the first step boundary
        self._loop_rng = rng
        wall_start = time.time()
        handler = self._preemption
        chaos_injectors = getattr(self, "_chaos_live", None)
        if chaos_injectors is None:
            chaos_injectors = list(self._chaos)
        # multi-host preemption must be AGREED: every process snapshots at
        # the same step or the shard files diverge. A small flag
        # all-gather decides — but it is a host-blocking cross-host round
        # trip, so it runs every BIGDL_PREEMPT_SYNC_EVERY steps (default
        # 10), not every step: a notice still resolves well inside the
        # grace window, and the hot loop keeps its async pipeline.
        sync_every = max(1, int(os.environ.get("BIGDL_PREEMPT_SYNC_EVERY",
                                               "10")))

        def preemption_agreed(neval: int) -> bool:
            local = handler is not None and handler.should_snapshot()
            if n_proc <= 1:
                return local
            if handler is None or neval % sync_every != 0:
                return False
            from jax.experimental import multihost_utils
            return bool(multihost_utils.process_allgather(
                np.asarray(1 if local else 0, np.int32)).max())

        # One-deep software pipeline: iteration i's loss is fetched AFTER
        # iteration i+1 is dispatched, so the host-side log/summary work and
        # the device->host sync overlap the device computing the next step
        # (an unpipelined float(loss) per step idles the device for the
        # round trip). Logs stay exact — each line reports its own
        # iteration's true loss, one dispatch later.
        pending = None  # in-flight dispatch awaiting its loss fetch
        last_done = None  # wall time the previous dispatch's losses landed
        tm = self._train_instruments()
        step_tracked = getattr(step, "tracked", step)  # ZeRO-1 wraps its TrackedJit

        def flush():
            nonlocal pending
            if pending is None:
                return
            p = pending
            pending = None
            # sync point: blocks until the dispatch is done
            t_sync = time.time()
            with span("train.sync", k=1, neval=p["neval"]):
                loss = float(np.asarray(p["loss"], np.float32))
            tm.sync.observe(time.time() - t_sync)
            with span("train.log", k=1, neval=p["neval"]):
                log_iteration(p, loss)

        def log_iteration(p, loss):
            """The host work after an iteration's loss fetch: metrics, MFU
            gauge, memory sample, the log line, summaries."""
            nonlocal last_done
            # inter-completion interval ~= per-dispatch device time in
            # steady state; measuring to the NEXT dispatch instead would
            # fold hook time and the next batch's data wait into
            # "computing time"
            done = time.time()
            iter_time = done - (last_done if last_done is not None
                                and last_done > p["t0"] else p["t0"])
            last_done = done
            first = p["neval"] == 1
            if first:
                # first step pays tracing+XLA compile (unless cached)
                self.metrics.add("compile and first-step time", iter_time)
            # live MFU: the step program's cost-analysis FLOPs over the
            # iteration's wall-clock and the chip's peak — absent when the
            # backend has no cost analysis or no known roof. The compile-
            # bearing first iteration is SKIPPED: its wall-clock is mostly
            # XLA, and publishing FLOPs/(compile+step) would trip any
            # dashboard threshold at every (re)start.
            if not first:
                m = profiling.mfu(getattr(step_tracked, "last_flops", None),
                                  iter_time)
                if m is not None:
                    tm.mfu.set(m)
            # step-boundary device-memory watermark (no-op on CPU)
            sample_device_memory()
            throughput = p["n_records"] / max(iter_time, 1e-9)
            tm.step.observe(iter_time)
            tm.steps.inc()
            tm.records.inc(p["n_records"])
            tm.rps.set(throughput)
            driver_state["trainingLoss"] = loss
            logger.info(
                "[Epoch %d %d/%d][Iteration %d][Wall %.3fs] Trained %d "
                "records in %.4fs. Throughput is %.1f records/second. "
                "Loss is %.5f.",
                p["epoch"], p["epoch_records"], p["size"], p["neval"],
                time.time() - wall_start, p["n_records"], iter_time,
                throughput, loss)
            self.metrics.add("computing time average", iter_time)
            if self.train_summary is not None:
                self.train_summary.add_scalar("Loss", loss, p["neval"])
                self.train_summary.add_scalar("Throughput", throughput,
                                              p["neval"])
                if p["lr"] is not None:
                    self.train_summary.add_scalar(
                        "LearningRate", float(p["lr"]), p["neval"])

        stop = False
        epoch_end = None  # the open train.epoch_end span, if any
        while not stop and not self.end_when(driver_state):
            self.dataset.shuffle()
            epoch = int(driver_state["epoch"])
            opt_state["epoch"] = jnp.asarray(epoch, jnp.int32)
            epoch_start = time.time()
            epoch_records = 0
            data_wait = 0.0
            t_data = time.time()
            ptrig = (self.train_summary.get_summary_trigger("Parameters")
                     if (self.train_summary is not None
                         and hasattr(self.train_summary,
                                     "get_summary_trigger")) else None)
            data_iter = iter(self.dataset.data(train=True))
            # tracked for deterministic teardown: an engine-backed iterator
            # owns worker threads; epoch end AND the exception path out of
            # optimize() close it explicitly instead of waiting on GC
            self._live_data_iter = data_iter
            epoch_batches = 0
            if (resume_cursor is not None
                    and int(resume_cursor.get("epoch", -1)) == epoch):
                # fast-forward past the batches the preempted run already
                # trained on: the resumed epoch continues where the
                # snapshot stopped instead of repeating it
                skip = int(resume_cursor.get("epoch_batches", 0))
                for _ in range(skip):
                    if next(data_iter, None) is None:
                        break
                epoch_batches = skip
                epoch_records = int(resume_cursor.get("epoch_records", 0))
            resume_cursor = None  # first resumed epoch only
            while True:
                neval = int(driver_state["neval"])
                if self._profile is not None:
                    # between two iterations, so the profile holds whole
                    # train.iteration spans
                    pdir, pstart, pn = self._profile
                    if self._profiling_active:
                        if neval >= pstart + pn:
                            self._stop_profile()
                            logger.info("[Profiler] trace for iterations "
                                        "%d-%d written to %s", pstart,
                                        neval - 1, pdir)
                    elif neval == pstart:
                        self._start_profile(pdir)
                if epoch_end is not None:
                    # the boundary ends where the next epoch's first
                    # iteration starts
                    epoch_end.__exit__(None, None, None)
                    epoch_end = None
                with tracing.step_span("train.iteration", neval,
                                       epoch=epoch) as iter_span:
                    with span("train.data", neval=neval, epoch=epoch):
                        batch = next(data_iter, None)
                        iter_span.annotate(k=0 if batch is None else 1)
                        if batch is not None:
                            dw = time.time() - t_data
                            data_wait += dw
                            tm.data_wait.observe(dw)
                    if batch is None:
                        # the iterator is exhausted: a pass with k=0 and
                        # no dispatch
                        break
                    t0 = time.time()
                    with span("train.dispatch", k=1, neval=neval):
                        data, labels = self._place_batch(batch)
                        params, buffers, opt_state, loss = step(
                            params, buffers, opt_state, rng.next_key(),
                            data, labels)
                    # host time enqueueing the step (async; device compute
                    # lands in the NEXT flush's sync wait)
                    tm.dispatch.observe(time.time() - t0)
                    flush()  # previous iteration: fetch loss, log, summarize
                    # snapshot the lr as its own small array NOW: opt_state's
                    # buffers are donated to the next dispatch and deleted
                    # (* 1 forces a fresh buffer if the schedule returns a state
                    # array by identity)
                    lr_arr = None
                    if (self.train_summary is not None
                            and hasattr(self.optim_method, "current_rate")):
                        lr_arr = self.optim_method.current_rate(opt_state)
                        if not isinstance(lr_arr, (int, float)):
                            lr_arr = lr_arr * 1
                    epoch_records += batch.size()
                    pending = {"loss": loss, "t0": t0, "neval": neval,
                               "epoch": epoch, "n_records": batch.size(),
                               "epoch_records": epoch_records,
                               "size": self.dataset.size(), "lr": lr_arr}
                    if ptrig is not None and ptrig(driver_state):
                        self._summarize_parameters(params, neval)
                    driver_state["neval"] = neval + 1
                    epoch_batches += 1
                    # the data-iterator cursor any checkpoint written at this
                    # boundary records in its RESUME marker
                    self._loop_cursor = {"epoch": epoch,
                                         "epoch_batches": epoch_batches,
                                         "epoch_records": epoch_records}
                    if uses_loss_any:
                        # loss-sensitive stop/hook triggers must see THIS
                        # iteration's loss, not the pipelined previous one
                        flush()
                    with span("train.hooks", neval=neval, epoch=epoch):
                        self._hooks(params, buffers, opt_state, driver_state,
                                    fwd, epoch_done=False, flush=flush)
                    for inj in chaos_injectors:
                        inj.on_step(neval)
                    if handler is not None:
                        fresh = handler.drain_notices()
                        if fresh:
                            instruments(get_registry()) \
                                .resilience_preemptions_total.inc(fresh)
                    if preemption_agreed(neval):
                        flush()
                        self._preempt_snapshot(params, buffers, opt_state,
                                               driver_state)
                    if self.end_when(driver_state):  # iteration/loss-based stops
                        stop = True
                        break
                    t_data = time.time()
            # from the drain of the epoch's last iteration to the first
            # iteration of the next epoch (closed there, or after the loop)
            epoch_end = span("train.epoch_end", epoch=epoch,
                             neval=int(driver_state["neval"]))
            epoch_end.__enter__()
            flush()  # drain the pipeline at epoch end (exact epoch log)
            self._close_data_iter()
            self.metrics.add("data wait time", data_wait)
            logger.info("[Epoch %d] Epoch finished. Wall clock time is %.1f ms (%d records)",
                        epoch, (time.time() - epoch_start) * 1e3, epoch_records)
            driver_state["epoch"] = epoch + 1
            with span("train.hooks", neval=int(driver_state["neval"]) - 1,
                      epoch=epoch):
                self._hooks(params, buffers, opt_state, driver_state, fwd,
                            epoch_done=True)

        if epoch_end is not None:
            epoch_end.__exit__(None, None, None)
        if self._profiling_active:  # window outran training: close the trace
            self._stop_profile()
        model.load_parameter_tree(self._finalize_params(params))
        model.load_buffer_tree(buffers)
        return model

    def _close_data_iter(self) -> None:
        """Close the tracked epoch iterator (no-op when none is live).
        Generator-backed pipelines run their ``finally`` blocks —
        engine-backed ones drain + join their stage threads — so the
        data-wait accounting and thread census stay exact on every exit
        path, exceptions included."""
        it = getattr(self, "_live_data_iter", None)
        self._live_data_iter = None
        if it is not None:
            close = getattr(it, "close", None)
            if close is not None:
                close()

    def _preempt_snapshot(self, params, buffers, opt_state,
                          driver_state) -> None:
        """End-of-step preemption snapshot: persist (model, state, RESUME
        marker) through the normal checkpoint machinery, leave the latest
        weights on the model object, and stop training via
        ``TrainingPreempted`` (never retried in-process — the host is
        being reclaimed; ``auto_resume()`` picks the snapshot up on
        relaunch, possibly on a different process count)."""
        reason = (self._preemption.reason
                  if self._preemption is not None and self._preemption.reason
                  else "preempted")
        if self._preemption is not None:
            # drain ingest first: a live reader/decode pipeline would race
            # shard reads and H2D transfers against snapshot IO inside the
            # grace window
            self._preemption.run_drain_hooks()
        final = self._finalize_params(params)
        snap_path = None
        if self.checkpoint_path is not None:
            t0 = time.time()
            with span("resilience.snapshot"):
                self._save_checkpoint(final, buffers, opt_state,
                                      driver_state)
            elapsed = time.time() - t0
            instruments(get_registry()).resilience_snapshot_seconds \
                .observe(elapsed)
            tag = ("" if self.is_overwrite
                   else f".{int(driver_state['neval'])}")
            snap_path = file_io.join(self.checkpoint_path, f"model{tag}")
            remaining = (self._preemption.remaining_grace()
                         if self._preemption is not None else float("inf"))
            logger.warning(
                "[Preempted] %s: snapshot %s written in %.2fs (grace "
                "remaining %.1fs); relaunch with auto_resume() to continue",
                reason, snap_path, elapsed, remaining)
        else:
            logger.warning("[Preempted] %s: no checkpoint path configured "
                           "— stopping WITHOUT a snapshot", reason)
        self.model.load_parameter_tree(final)
        self.model.load_buffer_tree(buffers)
        raise TrainingPreempted(reason, snap_path)

    def _summarize_parameters(self, params, neval: int) -> None:
        """Per-parameter histograms (reference ``TrainSummary`` "Parameters"
        trigger, ``DistriOptimizer.scala:410-440``)."""
        import jax.tree_util as jtu
        # sharded DistriOptimizer carries a flat padded vector; unravel it
        # back to the named pytree before logging per-parameter histograms
        flat = jtu.tree_flatten_with_path(self._finalize_params(params))[0]
        for path, leaf in flat:
            tag = "Parameters/" + "/".join(
                str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
            self.train_summary.add_histogram(tag, np.asarray(leaf), neval)

    # ------------------------------------------------------------------ hooks
    def _hooks(self, params, buffers, opt_state, driver_state, fwd,
               epoch_done: bool, flush=None) -> None:
        if (self.validation_trigger is not None
                and self.validation_trigger(driver_state)):
            self._validate(params, buffers, fwd, driver_state)
        if (self.checkpoint_trigger is not None
                and self.checkpoint_trigger(driver_state)):
            if flush is not None:
                flush()  # persist an exact driver_state (trainingLoss is
                # otherwise one pipelined iteration stale in the snapshot)
            self._save_checkpoint(self._finalize_params(params), buffers,
                                  opt_state, driver_state)

    def _run_validation(self, params, buffers, fwd):
        """(results, count) over the validation set; DistriOptimizer
        overrides for the multi-host per-process-shard + merge path."""
        from bigdl_tpu.optim.evaluator import evaluate_batches
        return evaluate_batches(
            fwd, params, buffers, self.validation_dataset.data(train=False),
            self.validation_methods, cache=self._eval_cache)

    def _validate(self, params, buffers, fwd, driver_state) -> None:
        if self.validation_dataset is None:
            return
        t0 = time.time()
        with span("train.validate"):
            results, count = self._run_validation(params, buffers, fwd)
        elapsed = time.time() - t0
        self.metrics.add("validation time", elapsed)
        self._train_instruments().validation.observe(elapsed)
        logger.info("[Validation] %d records in %.3fs. Throughput is %.1f records/s",
                    count, elapsed, count / max(elapsed, 1e-9))
        for i, (m, r) in enumerate(zip(self.validation_methods, results)):
            if r is None:
                continue
            logger.info("%s is %s", m.name, r)
            value = r.result()[0]
            if i == 0:
                # 'score' (used by Trigger.max_score) tracks the FIRST method.
                driver_state["score"] = value
            if self.validation_summary is not None:
                self.validation_summary.add_scalar(m.name, value,
                                                   int(driver_state["neval"]) - 1)
