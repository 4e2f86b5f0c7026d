"""Engine: process-global accelerator topology, the TPU analogue of
``utils/Engine.scala:32``.

The reference Engine parses Spark configs into (nExecutors x coresPerExecutor)
and owns two JVM thread pools that fan work out over cores. On TPU the unit of
parallelism is a *chip on a mesh*, not a core in a thread pool: XLA already
parallelises within a chip (MXU/VPU lanes), so ``Engine.model``-style intra-op
pools are unnecessary. What remains Engine's job:

- device discovery (``jax.devices()``), local vs. global counts (multi-host),
- construction of the default `jax.sharding.Mesh` used by DistriOptimizer,
- a small host-side IO thread pool (data pipeline prefetch — the one place
  host threads still matter, replacing ``Engine.default``),
- environment sanity checks (the analogue of ``Engine.checkSparkContext``).
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
import logging
from typing import List, Optional, Sequence

import numpy as np

logger = logging.getLogger("bigdl_tpu")


class _EngineState:
    def __init__(self) -> None:
        self.initialized = False
        self.dist_checked = False
        self.env_warned: set = set()
        self.node_number = 1
        self.core_number = 1
        self._devices = None
        self._mesh = None
        self._io_pool: Optional[ThreadPoolExecutor] = None
        self._lock = threading.Lock()


_state = _EngineState()

#: Where compiled programs persist when the environment names no place: a
#: fixed path inside the checkout (``.gitignore`` lists it). The path is
#: part of the cache key, so it never derives from $TMPDIR, a pid or a time.
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir() -> str:
    """The one compile-cache rule; returns the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    nothing is set in code. Where it is not, the cache goes to
    ``<checkout>/.jax_cache``. ``import bigdl_tpu`` calls this, so every
    entry point (apps, ``chip_smoke.py``, the benchmark, scripts) follows
    the same rule and a second run of any of them starts warm."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    if jax.config.jax_compilation_cache_dir != _CHECKOUT_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE_DIR)
    return _CHECKOUT_CACHE_DIR


class Engine:
    """Process-global topology singleton (reference ``utils/Engine.scala``)."""

    @staticmethod
    def init(node_number: Optional[int] = None,
             core_number: Optional[int] = None,
             devices: Optional[Sequence] = None) -> None:
        """Initialise topology.

        ``node_number``/``core_number`` retain the reference's names
        (``Engine.init`` at ``utils/Engine.scala:100``) but map to hosts and
        local chips. With no arguments, discovers the JAX runtime topology.
        """
        Engine._maybe_init_distributed()
        import jax

        with _state._lock:
            _state._devices = list(devices) if devices is not None else jax.devices()
            _state.node_number = node_number if node_number is not None else jax.process_count()
            _state.core_number = (core_number if core_number is not None
                                  else max(1, len(_state._devices) // max(1, _state.node_number)))
            _state._mesh = None  # rebuilt lazily against the new device set
            _state.initialized = True
        # pin the native runtime's host threads to the declared core budget
        # (reference ThreadPool.setMKLThread / MKL.setNumThreads)
        try:
            from bigdl_tpu import native
            native.set_num_threads(_state.core_number)
        except Exception:  # pragma: no cover - native layer is optional
            pass
        Engine.check_env()

    @staticmethod
    def check_env(strict: bool = False) -> List[str]:
        """Verify the launch environment the way the reference verifies its
        required spark conf (``Engine.checkSparkContext``,
        ``utils/Engine.scala:269-293`` against ``spark-bigdl.conf:31-43``).

        ``scripts/bigdl-tpu.sh`` sets this; a bare ``python`` invocation
        gets a warning (or, with ``strict=True`` ≙ the reference's
        ``forceCheck``, an error) listing what's off. Returns the list of
        complaint strings. Suppress with ``BIGDL_TPU_DISABLE_ENV_CHECK=1``
        (reference ``bigdl.disableCheckSysEnv``). The compile cache is not
        checked: ``compile_cache_dir`` gives every process one."""
        problems: List[str] = []
        disable = os.environ.get("BIGDL_TPU_DISABLE_ENV_CHECK", "")
        if disable.strip().lower() in ("1", "true", "yes", "y", "on"):
            return problems
        omp = os.environ.get("OMP_NUM_THREADS")
        if omp is not None:
            omp = omp.strip()
        if omp is None or not omp.isdigit() or not 1 <= int(omp) <= 4:
            problems.append(
                f"OMP_NUM_THREADS={omp or '<unset>'}: host BLAS/OpenMP "
                "threads fight the data-pipeline IO pool; the launcher "
                "pins it to 1 (reference spark-bigdl.conf OMP_NUM_THREADS=1)")
        # warn once per process per complaint — library-style users re-init
        # Engine freely and should not see the same nag every time
        for p in problems:
            if p not in _state.env_warned:
                _state.env_warned.add(p)
                logger.warning("[Engine.check_env] %s", p)
        if strict and problems:
            raise RuntimeError("launch environment check failed:\n  "
                               + "\n  ".join(problems))
        return problems

    @staticmethod
    def _maybe_init_distributed() -> None:
        """Multi-host bring-up: ``jax.distributed.initialize`` from env.

        The reference parses its cluster topology out of spark-submit
        properties (``utils/Engine.scala:346-416``); here the launcher
        exports a coordinator endpoint instead:

        - ``BIGDL_COORDINATOR_ADDRESS`` (or ``JAX_COORDINATOR_ADDRESS``) —
          host:port of process 0's coordination service,
        - ``BIGDL_NUM_PROCESSES`` / ``BIGDL_PROCESS_ID`` (or the JAX names).

        On a real TPU pod slice none of these are needed (JAX auto-detects
        via the TPU metadata server) — initialize is then a no-arg call,
        triggered by ``BIGDL_AUTO_DISTRIBUTED=1``. Idempotent.
        """
        if _state.dist_checked:
            return
        coord = (os.environ.get("BIGDL_COORDINATOR_ADDRESS")
                 or os.environ.get("JAX_COORDINATOR_ADDRESS"))
        auto = os.environ.get("BIGDL_AUTO_DISTRIBUTED", "0") == "1"
        if not coord and not auto:
            _state.dist_checked = True
            return
        import jax
        if jax.distributed.is_initialized():
            _state.dist_checked = True
            return
        # (CPU multi-process collectives ride gloo, jax's default.)
        # A genuine connect failure must RAISE: swallowing it would let N
        # hosts silently train independently against one checkpoint path.
        if coord:
            nproc = (os.environ.get("BIGDL_NUM_PROCESSES")
                     or os.environ.get("JAX_NUM_PROCESSES"))
            pid = (os.environ.get("BIGDL_PROCESS_ID")
                   or os.environ.get("JAX_PROCESS_ID"))
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=int(nproc) if nproc else None,
                process_id=int(pid) if pid else None)
        else:
            jax.distributed.initialize()
        _state.dist_checked = True
        if jax.process_index() != 0:
            # driver-style logging: per-iteration INFO only on process 0
            # (reference logs on the Spark driver only)
            import logging
            logging.getLogger("bigdl_tpu.optim").setLevel(logging.WARNING)

    @staticmethod
    def process_index() -> int:
        """This host's rank (0 = the 'driver' for logging/checkpoint IO)."""
        Engine._maybe_init_distributed()  # before the backend freezes
        import jax
        return jax.process_index()

    @staticmethod
    def process_count() -> int:
        Engine._maybe_init_distributed()
        import jax
        return jax.process_count()

    @staticmethod
    def local_devices():
        import jax
        return jax.local_devices()

    @staticmethod
    def is_initialized() -> bool:
        return _state.initialized

    @staticmethod
    def node_number() -> int:
        Engine._ensure()
        return _state.node_number

    @staticmethod
    def core_number() -> int:
        Engine._ensure()
        return _state.core_number

    @staticmethod
    def devices():
        Engine._ensure()
        return list(_state._devices)

    @staticmethod
    def device_count() -> int:
        return len(Engine.devices())

    @staticmethod
    def default_mesh(axis_name: str = "data"):
        """The 1-D data-parallel mesh over all devices.

        This is the TPU-native stand-in for the reference's implicit
        "one partition per executor" topology (``AllReduceParameter`` slice
        ownership): every chip holds a full replica, gradients are reduced by
        an XLA ``psum`` riding ICI instead of BlockManager fetches.
        """
        from jax.sharding import Mesh

        Engine._ensure()
        if _state._mesh is None or _state._mesh.axis_names != (axis_name,):
            devs = np.array(Engine.devices())
            _state._mesh = Mesh(devs, (axis_name,))
        return _state._mesh

    @staticmethod
    def io_pool() -> ThreadPoolExecutor:
        """Host-side IO/prefetch pool (descendant of ``Engine.default``,
        ``utils/Engine.scala:236-241`` — here only for the data pipeline)."""
        Engine._ensure()
        if _state._io_pool is None:
            n = int(os.environ.get("BIGDL_TPU_IO_THREADS", str(min(16, os.cpu_count() or 4))))
            _state._io_pool = ThreadPoolExecutor(max_workers=n, thread_name_prefix="bigdl-io")
        return _state._io_pool

    @staticmethod
    def check_singleton() -> bool:
        """One training process per host (reference ``Engine.checkSingleton``,
        ``utils/Engine.scala:160`` — there a JVM-wide flag; here an exclusive
        host lock file keyed by $BIGDL_SINGLETON_DIR). Returns True when this
        process holds (or just acquired) the claim; False when another live
        process holds it. Disabled unless BIGDL_CHECK_SINGLETON=1, matching
        the reference's ``bigdl.check.singleton`` property."""
        import os
        if os.environ.get("BIGDL_CHECK_SINGLETON", "0") != "1":
            return True
        import tempfile
        lock_dir = os.environ.get("BIGDL_SINGLETON_DIR",
                                  tempfile.gettempdir())
        path = os.path.join(lock_dir, "bigdl_tpu.singleton.lock")
        pid = os.getpid()

        def try_claim() -> bool:
            # write pid to a private file, then hard-link it into place —
            # link(2) is atomic, so exactly one contender wins and the lock
            # file is never observable with partial/empty contents
            tmp = f"{path}.{pid}"
            try:
                with open(tmp, "w") as f:
                    f.write(str(pid))
                os.link(tmp, path)
                return True
            except FileExistsError:
                return False
            finally:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass

        if try_claim():
            return True
        try:
            holder = int(open(path).read().strip() or "0")
        except (OSError, ValueError):
            holder = 0
        if holder == pid:
            return True
        if holder:
            try:
                os.kill(holder, 0)  # probe liveness
                return False  # live holder
            except ProcessLookupError:
                pass  # stale lock from a dead process — take it over
            except PermissionError:
                return False  # live process of another user holds it
        else:
            return False  # unreadable/foreign lock: don't steal
        try:
            os.unlink(path)
        except OSError:
            pass
        return try_claim()  # only one stale-lock contender wins the link

    @staticmethod
    def reset() -> None:
        """Forget topology (test hook, analogue of re-running Engine.init)."""
        with _state._lock:
            if _state._io_pool is not None:
                _state._io_pool.shutdown(wait=False)
            _state.__init__()

    @staticmethod
    def _ensure() -> None:
        if not _state.initialized:
            Engine.init()
