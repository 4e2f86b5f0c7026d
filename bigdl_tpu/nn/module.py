"""Module protocol: Torch-style stateful API over a functional JAX core.

Reference parity: ``nn/abstractnn/AbstractModule.scala:50`` — the reference's
modules are mutable objects with imperative ``forward``/``backward``, cached
``output``/``gradInput``, and hand-written per-layer gradients. A line-for-line
port would fight XLA (Python-side mutation can't be traced). The TPU-native
design splits the two roles the reference conflates:

1. **Module objects** (this file) hold hyper-parameters, parameter *values*,
   and the ``forward`` computation written in ordinary jax.numpy. They keep the
   reference's ergonomics: ``Sequential().add(Linear(2, 3)).add(ReLU())``,
   ``module.forward(x)``, ``module.parameters()``, train/eval mode.

2. **functional_apply(module, params, buffers, ...)** re-expresses any module
   as a *pure function* of a parameter pytree. Everything the optimizer jits —
   forward, loss, gradients (via ``jax.grad``, replacing the reference's
   hand-written ``updateGradInput``/``accGradParameters``), and the SPMD
   collectives — goes through this pure view. The module object's arrays are
   snapshotted and restored around the traced call, so tracing never leaks
   tracers into user-visible state.

Gradients come from autodiff rather than per-layer backward methods; the
``backward(input, grad_output)`` API is still provided (via ``jax.vjp``) for
reference-parity and tests.
"""

from __future__ import annotations

import contextvars
import copy
import threading
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.utils.rng import RandomGenerator
from bigdl_tpu.utils.table import Table

Activity = Union[jax.Array, Table, Tuple, List]


class RngStream:
    """Splittable PRNG stream bound during functional apply (dropout etc.)."""

    def __init__(self, key: Optional[jax.Array]):
        self._key = key

    def next_key(self) -> jax.Array:
        if self._key is None:
            # Eager convenience path: draw from the global generator.
            return RandomGenerator.RNG().next_key()
        self._key, sub = jax.random.split(self._key)
        return sub


_RNG_CTX: contextvars.ContextVar[Optional[RngStream]] = contextvars.ContextVar(
    "bigdl_tpu_rng", default=None)


def current_rng() -> RngStream:
    stream = _RNG_CTX.get()
    if stream is None:
        return RngStream(None)
    return stream


# ---------------------------------------------------------------- profiling
# Reference per-module timing: ``AbstractModule.scala:134-145`` accumulates
# forwardTime/backwardTime on every call. Under jit that is meaningless (XLA
# fuses the whole step), so the TPU build offers two complementary tools:
#  - ``jax.named_scope(module.name)`` is ALWAYS applied around update_output,
#    so compiled instructions carry module names; ``Optimizer.set_profiling``
#    reduces a profile of the step to device time by layer and pass
#    (``step_partition.json``; ``telemetry/step_partition.py``);
#  - opt-in EAGER timing (``enable_timing``): outside jit, each forward/
#    backward blocks on its result and accumulates wall time, read back via
#    ``get_times()`` exactly like the reference.
_TIMING_ENABLED = False


def enable_timing(flag: bool = True) -> None:
    """Turn on eager per-module wall-time accumulation (get_times()).
    Off by default: blocking after every module defeats async dispatch."""
    global _TIMING_ENABLED
    _TIMING_ENABLED = flag


def _tracing_now() -> bool:
    try:
        from jax._src import core as _core
        return not _core.trace_state_clean()
    except Exception:  # pragma: no cover - fallback on jax internals drift
        return False


class Module:
    """Base module (reference ``AbstractModule``).

    Subclasses declare parameters/buffers in ``__init__`` via
    ``register_parameter``/``register_buffer`` (or by assigning the result of
    an init helper) and implement ``update_output(*inputs)`` using jax.numpy.
    """

    def __init__(self, name: Optional[str] = None):
        d = object.__setattr__
        d(self, "_parameters", {})   # name -> jax.Array (trainable)
        d(self, "_buffers", {})      # name -> jax.Array (running stats etc.)
        d(self, "_modules", {})      # name -> Module
        d(self, "training", True)
        d(self, "name", name or type(self).__name__)
        d(self, "output", None)
        d(self, "grad_input", None)
        d(self, "_param_regularizers", {})  # name -> Regularizer or None

    # ------------------------------------------------------------------ state
    def register_parameter(self, name: str, value, regularizer=None) -> None:
        self._parameters[name] = jnp.asarray(value)
        if regularizer is not None:
            self._param_regularizers[name] = regularizer

    def register_buffer(self, name: str, value) -> None:
        self._buffers[name] = jnp.asarray(value)

    def add_module(self, name: str, module: "Module") -> None:
        self._modules[name] = module

    def __setattr__(self, name: str, value: Any) -> None:
        if isinstance(value, Module):
            self.__dict__.pop(name, None)  # module registry wins over plain attr
            self._modules[name] = value
        elif name in self._parameters:
            self._parameters[name] = value
        elif name in self._buffers:
            self._buffers[name] = value
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name: str) -> Any:
        # Called only when normal lookup fails.
        for store in ("_parameters", "_buffers", "_modules"):
            d = object.__getattribute__(self, store)
            if name in d:
                return d[name]
        raise AttributeError(f"{type(self).__name__!s} has no attribute {name!r}")

    # Pytree views -----------------------------------------------------------
    def parameter_tree(self) -> Dict[str, Any]:
        tree = dict(self._parameters)
        for name, child in self._modules.items():
            sub = child.parameter_tree()
            if sub:
                tree[name] = sub
        return tree

    def buffer_tree(self) -> Dict[str, Any]:
        tree = dict(self._buffers)
        for name, child in self._modules.items():
            sub = child.buffer_tree()
            if sub:
                tree[name] = sub
        return tree

    def load_parameter_tree(self, tree: Dict[str, Any]) -> None:
        for name in self._parameters:
            if name in tree:
                self._parameters[name] = tree[name]
        for name, child in self._modules.items():
            if name in tree:
                child.load_parameter_tree(tree[name])

    def load_buffer_tree(self, tree: Dict[str, Any]) -> None:
        for name in self._buffers:
            if name in tree:
                self._buffers[name] = tree[name]
        for name, child in self._modules.items():
            if name in tree:
                child.load_buffer_tree(tree[name])

    def named_modules(self, prefix: str = "") -> List[Tuple[str, "Module"]]:
        out = [(prefix or self.name, self)]
        for name, child in self._modules.items():
            out.extend(child.named_modules(f"{prefix}.{name}" if prefix else name))
        return out

    def modules(self) -> List["Module"]:
        return [m for _, m in self.named_modules()]

    def apply_to_modules(self, fn: Callable[["Module"], None]) -> "Module":
        for m in self.modules():
            fn(m)
        return self

    def __call__(self, *inputs: Activity) -> Activity:
        return self.forward(*inputs)

    def regularizer_tree(self) -> Dict[str, Any]:
        """Pytree (matching parameter_tree) of per-parameter regularizers."""
        tree = {name: self._param_regularizers.get(name)
                for name in self._parameters}
        for name, child in self._modules.items():
            sub = child.regularizer_tree()
            if sub:
                tree[name] = sub
        return tree

    # ---------------------------------------------------------------- forward
    def update_output(self, *inputs: Activity) -> Activity:
        raise NotImplementedError

    def forward(self, *inputs: Activity) -> Activity:
        if _TIMING_ENABLED and not _tracing_now():
            import time as _time
            t0 = _time.perf_counter()
            with jax.named_scope(self.name):
                out = self.update_output(*inputs)
            out = jax.block_until_ready(out)
            # container time includes children (each child also self-times)
            self._forward_time = (getattr(self, "_forward_time", 0.0)
                                  + _time.perf_counter() - t0)
            self.output = out
            return out
        with jax.named_scope(self.name):
            self.output = self.update_output(*inputs)
        return self.output

    def backward(self, input: Activity, grad_output: Activity) -> Activity:
        """Input gradient via autodiff (parity with reference ``backward``;
        the training loop itself uses ``jax.grad`` over the whole loss)."""
        import time as _time
        timing = _TIMING_ENABLED and not _tracing_now()
        t0 = _time.perf_counter() if timing else 0.0
        params = self.parameter_tree()
        buffers = self.buffer_tree()

        def fwd(p, x):
            out, _ = functional_apply(self, p, buffers, x, training=self.training)
            return out

        _, vjp = jax.vjp(lambda x: fwd(params, x), input)
        self.grad_input = vjp(grad_output)[0]
        if timing:
            self.grad_input = jax.block_until_ready(self.grad_input)
            self._backward_time = (getattr(self, "_backward_time", 0.0)
                                   + _time.perf_counter() - t0)
        return self.grad_input

    # ------------------------------------------------------------- profiling
    def get_times(self) -> List[Tuple["Module", float, float]]:
        """Per-module (module, forward_s, backward_s), depth-first — the
        reference's ``getTimes`` (``AbstractModule.scala:134-145``;
        aggregated over containers ``Container.scala:88-95``). Populated only
        while ``nn.module.enable_timing(True)`` and outside jit: the EAGER
        path only. Under jit, ``Optimizer.set_profiling`` writes the step's
        device time by layer and pass (``step_partition.json``)."""
        times = [(self, getattr(self, "_forward_time", 0.0),
                  getattr(self, "_backward_time", 0.0))]
        for child in self._modules.values():
            times.extend(child.get_times())
        return times

    def reset_times(self) -> None:
        """reference ``resetTimes``."""
        self._forward_time = 0.0
        self._backward_time = 0.0
        for child in self._modules.values():
            child.reset_times()

    def time_report(self) -> str:
        """Human-readable get_times() table (debug aid; the eager path, as
        ``get_times``)."""
        lines = ["module                                  fwd(s)    bwd(s)"]
        for m, f, b in self.get_times():
            lines.append(f"{type(m).__name__ + ' (' + m.name + ')':38s} "
                         f"{f:8.4f}  {b:8.4f}")
        return "\n".join(lines)

    # -------------------------------------------------------------- lifecycle
    def reset(self) -> None:
        """Re-initialise parameters (layers override)."""
        for child in self._modules.values():
            child.reset()

    def training_mode(self) -> "Module":
        self.training = True
        for child in self._modules.values():
            child.training_mode()
        return self

    def evaluate_mode(self) -> "Module":
        self.training = False
        for child in self._modules.values():
            child.evaluate_mode()
        return self

    # Reference-named aliases (AbstractModule.training()/evaluate()).
    def set_training(self, is_training: bool = True) -> "Module":
        return self.training_mode() if is_training else self.evaluate_mode()

    def is_training(self) -> bool:
        return self.training

    def clone_module(self) -> "Module":
        return copy.deepcopy(self)

    # Per-instance attachment caches that must NEVER serialize or deepcopy
    # with the module: compiled-program caches (jit wrappers hold live XLA
    # executables) and the serving prefix trie (holds a threading.Lock —
    # unpicklable — plus cached KV snapshots that would silently multiply
    # a checkpoint or a clone_module() by the cache size). Every site that
    # attaches a cache via ``model.__dict__`` must list it here; the
    # serialization regression test walks this tuple.
    _EPHEMERAL_CACHES = (
        "_jit_forward",    # nn.module: per-signature forward programs
        "_generate_fns",   # models.generation: decode program LRU
        "_spec_fns",       # models.generation: speculative-decode programs
        "_prefix_trie",    # models.prefix_cache: cross-request KV snapshots
    )

    def __getstate__(self):
        d = self.__dict__.copy()
        for key in self._EPHEMERAL_CACHES:
            d.pop(key, None)
        return d

    # ----------------------------------------------------- parameter flatten
    def parameters(self) -> List[jax.Array]:
        """All trainable arrays, depth-first (reference returns
        (weights, grads); grads have no stateful analogue here)."""
        return jax.tree_util.tree_leaves(self.parameter_tree())

    def get_parameters(self) -> Tuple[jax.Array, Callable[[jax.Array], Dict]]:
        """Flat contiguous parameter vector + unravel fn.

        Reference parity: ``Module.flatten`` / ``getParameters()``
        (``nn/Module.scala:40-68``) builds one contiguous storage so the flat
        all-reduce can exchange a single buffer. Under XLA the flat view is a
        *functional* ravel: collectives operate on the pytree directly, but
        the flat vector remains the contract for checkpoint compatibility and
        the parameter-sharded optimizer update.
        """
        from jax.flatten_util import ravel_pytree
        flat, unravel = ravel_pytree(self.parameter_tree())
        return flat, unravel

    def n_parameters(self) -> int:
        return sum(int(np.prod(p.shape)) for p in self.parameters())

    def summary(self, max_depth: int = 2) -> str:
        """Human-readable per-module parameter table (depth-limited), plus
        totals — a quick structural sanity check before training.

        Examples::

            >>> from bigdl_tpu import nn
            >>> m = (nn.Sequential().add(nn.Linear(4, 8).set_name("fc1"))
            ...      .add(nn.ReLU()).add(nn.Linear(8, 2).set_name("fc2")))
            >>> print(m.summary())  # doctest: +ELLIPSIS
            Sequential...
            ...fc1...40
            ...fc2...18
            ...
            Total parameters: 58
        """
        lines = []

        def walk(mod, depth, label):
            collapsed = depth >= max_depth or not mod._modules
            count = mod.n_parameters() if collapsed else sum(
                int(np.prod(p.shape)) for p in mod._parameters.values())
            lines.append(f"{'  ' * depth}{label} ({type(mod).__name__})"
                         .ljust(52) + f"{count:>12,}")
            if depth < max_depth:
                for key, child in mod._modules.items():
                    # registry key distinguishes default-named siblings
                    label = child.name if child.name != type(child).__name__ \
                        else f"{key}:{child.name}"
                    walk(child, depth + 1, label)

        walk(self, 0, self.name)
        lines.append("-" * 64)
        lines.append(f"Total parameters: {self.n_parameters():,}")
        return "\n".join(lines)

    def zero_grad_parameters(self) -> None:
        """No-op: gradients are values returned by ``jax.grad``, never state."""

    # ---------------------------------------------------------------- helpers
    def set_name(self, name: str) -> "Module":
        self.name = name
        return self

    def get_name(self) -> str:
        return self.name

    def find_module(self, name: str) -> Optional["Module"]:
        """Lookup by name anywhere in the tree (reference ``apply(name)``)."""
        for _, m in self.named_modules():
            if m.name == name:
                return m
        return None

    def rng_key(self) -> jax.Array:
        """Fresh PRNG key from the bound stream (dropout, rrelu, ...)."""
        return current_rng().next_key()

    def __repr__(self) -> str:
        child_repr = "".join(
            f"\n  ({n}): " + repr(m).replace("\n", "\n  ")
            for n, m in self._modules.items())
        return f"{type(self).__name__}({child_repr}\n)" if child_repr else f"{type(self).__name__}()"

    # ------------------------------------------------------------- inference
    def _jitted_forward(self):
        """Cached jitted pure forward — one compile per module instance."""
        fn = self.__dict__.get("_jit_forward")
        if fn is None:
            fn = jit_apply(self)
            self.__dict__["_jit_forward"] = fn
        return fn

    def predict(self, x: Activity) -> Activity:
        was_training = self.training
        self.evaluate_mode()
        try:
            params, buffers = self.parameter_tree(), self.buffer_tree()
            out, _ = self._jitted_forward()(params, buffers, x, training=False)
            return out
        finally:
            self.set_training(was_training)

    def functional_state(self) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """Atomic ``(parameter_tree, buffer_tree)`` snapshot.

        Taken under the functional_apply lock so a concurrent trace's
        temporarily-loaded tracers can never be observed (the unlocked
        ``parameter_tree()`` read racing another thread's apply window was
        the round-1 thread-safety hazard)."""
        with _apply_lock(self):
            return self.parameter_tree(), self.buffer_tree()

    def predict_class(self, x: jax.Array) -> jax.Array:
        """1-based class prediction (Torch label convention,
        reference ``AbstractModule.predictClass``)."""
        out = self.predict(x)
        return jnp.argmax(out, axis=-1) + 1

    def evaluate(self, dataset, methods):
        """Batch evaluation (reference ``AbstractModule.evaluate`` →
        ``optim/Evaluator.scala``)."""
        from bigdl_tpu.optim.evaluator import Evaluator
        return Evaluator(self).test(dataset, methods)


class TensorModule(Module):
    """Tensor→Tensor module marker (reference ``TensorModule``)."""


# --------------------------------------------------------------------------
# Functional view
# --------------------------------------------------------------------------

# Per-root-module reentrant locks serializing the load/forward/restore window
# of functional_apply. Kept out-of-object (weak-keyed) so modules stay
# picklable and deep-copyable; RLock keeps nested applies on the same root
# (same thread) legal.
_APPLY_LOCKS: "weakref.WeakKeyDictionary[Module, threading.RLock]" = (
    weakref.WeakKeyDictionary())
_APPLY_LOCKS_GUARD = threading.Lock()


def _apply_lock(module: Module) -> threading.RLock:
    with _APPLY_LOCKS_GUARD:
        lock = _APPLY_LOCKS.get(module)
        if lock is None:
            lock = threading.RLock()
            _APPLY_LOCKS[module] = lock
        return lock


def functional_apply(module: Module,
                     params: Dict[str, Any],
                     buffers: Dict[str, Any],
                     *inputs: Activity,
                     training: bool = False,
                     rng: Optional[jax.Array] = None,
                     ) -> Tuple[Activity, Dict[str, Any]]:
    """Run ``module.forward`` as a pure function of (params, buffers).

    Returns ``(output, new_buffers)``. Safe to trace: the module's concrete
    arrays are snapshotted before and restored after, so a ``jit`` trace never
    leaves tracers behind in the module object.

    Thread safety: the load/forward/restore window mutates shared module
    state, so concurrent applies on the same root module (e.g. two Evaluator
    threads) are serialized by a per-root reentrant lock.
    """
    with _apply_lock(module):
        old_params = module.parameter_tree()
        old_buffers = module.buffer_tree()
        old_training = module.training
        token = _RNG_CTX.set(RngStream(rng))
        try:
            module.load_parameter_tree(params)
            module.load_buffer_tree(buffers)
            module.set_training(training)
            out = module.forward(*inputs)
            new_buffers = module.buffer_tree()
        finally:
            _RNG_CTX.reset(token)
            module.load_parameter_tree(old_params)
            module.load_buffer_tree(old_buffers)
            module.set_training(old_training)
            for m in module.modules():  # no tracers retained in the tree
                m.output = None
                m.grad_input = None
    return out, new_buffers


def jit_apply(module: Module) -> Callable:
    """Jitted pure forward: ``f(params, buffers, *inputs, training=...)``."""
    def fn(params, buffers, *inputs, training=False, rng=None):
        return functional_apply(module, params, buffers, *inputs,
                                training=training, rng=rng)
    return jax.jit(fn, static_argnames=("training",))
