"""Device-resident dataset cache — the TPU-native ``CachedDistriDataSet``.

The reference caches each partition's samples in executor memory once and
re-shuffles only an index array per epoch (``dataset/DataSet.scala:240,
292-299``: "shuffle = reshuffle indexes only"); batches are then collated
from the cached samples. The TPU-native descendant goes one step further:
the whole (deterministically transformed) dataset lives ON DEVICE as one
stacked feature/label array pair, each epoch draws a fresh SAMPLE-level
permutation (same composition semantics as the reference — batch membership
changes every epoch), and batches are produced by on-device gathers.

Why it exists: without it every iteration re-stacks the batch on the host
(~154 MB for ResNet-50 b=256 in fp32) and pushes it host-to-device again.
With the cache, the transfer happens once and an epoch costs one (N,)-int
permutation upload plus device gathers.

Limits, by design:
- the wrapped dataset must be finite and fit device memory next to the
  model (a (N, 224, 224, 3) f32 cache is N x 602 KB);
- RANDOM host augmentations (random crop/flip/jitter) must NOT sit below
  the cache — they would be frozen at materialization. Enforced: stages
  marked ``stochastic`` in the wrapped chain raise at materialization.
"""

from __future__ import annotations

from typing import Iterator, Optional

import numpy as np

from bigdl_tpu.dataset.base import AbstractDataSet, MiniBatch, Sample
from bigdl_tpu.utils.rng import RandomGenerator


class CachedSliceBatch:
    """Lazy MiniBatch: indices into the device cache, gathered on access.

    ``data``/``labels`` are properties, so ``jnp.asarray(batch.data)``
    triggers the gather."""

    __slots__ = ("source", "idx")

    def __init__(self, source: "DeviceCachedDataSet", idx):
        self.source = source
        self.idx = idx

    @property
    def data(self):
        return self.source._x[self.idx]

    @property
    def labels(self):
        return self.source._y[self.idx]

    def size(self) -> int:
        return int(self.idx.shape[0])

    def __iter__(self):
        yield self.data
        yield self.labels


class DeviceCachedDataSet(AbstractDataSet[MiniBatch]):
    """Materialize a Sample-level dataset on device once; serve shuffled
    MiniBatches via on-device gathers.

    >>> import numpy as np
    >>> from bigdl_tpu.dataset.base import DataSet, Sample
    >>> ds = DeviceCachedDataSet(DataSet.array(
    ...     [Sample(np.full((2,), i, np.float32), float(i % 2 + 1))
    ...      for i in range(8)]), batch_size=4)
    >>> batches = list(ds.data(train=False))
    >>> [int(b.size()) for b in batches]
    [4, 4]
    """

    def __init__(self, base: AbstractDataSet[Sample], batch_size: int,
                 cast_dtype: Optional[str] = None):
        if batch_size <= 0:
            raise ValueError(f"batch_size must be positive, got {batch_size}")
        self.base = base
        self.batch_size = batch_size
        # transfer dtype for features (e.g. "bfloat16" halves H2D bytes AND
        # cache footprint when the compute policy is bf16 anyway)
        self.cast_dtype = cast_dtype
        self._x = None
        self._y = None
        self._perm = None
        self._mesh = None
        self._data_axis = None
        self._gather_fn = None

    def set_mesh(self, mesh, data_axis: str = "data") -> None:
        """Shard the cache over the mesh's data axis (the reference's
        per-partition `CachedDistriDataSet`, taken SPMD). Called by
        DistriOptimizer before materialization; shuffling then permutes
        WITHIN each shard (reference semantics: each partition reshuffles
        its own indexes) and batches are per-shard ``shard_map`` gathers —
        local by construction, no cross-device data motion."""
        if self._x is not None and self._mesh is not mesh:
            raise RuntimeError("DeviceCachedDataSet already materialized; "
                               "set_mesh must precede the first epoch")
        if data_axis in mesh.shape and mesh.shape[data_axis] > 1:
            self._mesh = mesh
            self._data_axis = data_axis
        # a 1-wide (or absent) data axis degenerates to the local cache

    # ------------------------------------------------------------------ cache
    def _scan_for_stochastic_stages(self) -> None:
        """Refuse to freeze random augmentation: a stochastic stage (random
        crop/flip/jitter) below the cache would be drawn ONCE and re-served
        every epoch — silent model-quality damage, so it is an error."""
        from bigdl_tpu.dataset.base import (TransformedDataSet,
                                            _flatten_chain)
        ds = self.base
        while isinstance(ds, TransformedDataSet):
            for stage in _flatten_chain(ds.transformer):
                if getattr(stage, "stochastic", False):
                    raise ValueError(
                        f"DeviceCachedDataSet cannot cache below the "
                        f"stochastic stage {type(stage).__name__}: its "
                        "random draw would be frozen at materialization. "
                        "Keep random augmentation out of the cached chain "
                        "(or use the host collate path).")
            ds = ds.base

    def _materialize(self) -> None:
        if self._x is not None:
            return
        import time as _time
        t_fill = _time.perf_counter()
        try:
            self._materialize_inner()
        finally:
            # cold-start attribution (docs/OBSERVABILITY.md): the first
            # step blocks on this whole-cache build — charge it to the
            # ingest stall ledger so "why was step 1 slow" has an answer
            # instead of vanishing into data-wait noise
            from bigdl_tpu.telemetry import get_registry, instruments
            instruments(get_registry()).ingest_stall_seconds_total.labels(
                stage="materialize").inc(_time.perf_counter() - t_fill)

    def _materialize_inner(self) -> None:
        from bigdl_tpu.telemetry import span
        self._scan_for_stochastic_stages()
        import jax.numpy as jnp
        feats, labels = [], []
        with span("ingest.materialize", batch_size=self.batch_size):
            for s in self.base.data(train=False):
                # Sample has .feature; the image types (LabeledImage) carry
                # the array as .data with the same (feature, label) meaning
                feats.append(s.feature if hasattr(s, "feature") else s.data)
                labels.append(s.label)
        if not feats:
            raise ValueError("DeviceCachedDataSet: wrapped dataset is empty")
        if self._mesh is None and len(feats) < self.batch_size:
            # batch_size is GLOBAL; under a multi-process mesh the local
            # record count is a per-process slice — the sharded branch
            # checks the global total itself
            raise ValueError(
                f"DeviceCachedDataSet: {len(feats)} samples cannot fill one "
                f"batch of {self.batch_size}")
        x = np.stack(feats)
        if self.cast_dtype:
            import ml_dtypes  # noqa: F401 - registers bfloat16 with numpy
            x = x.astype(self.cast_dtype)
        y = np.stack([np.asarray(l) for l in labels])
        if y.ndim == 2 and y.shape[1] == 1:
            y = y[:, 0]  # SampleToBatch's (N,1)->(N,) label squeeze parity
        if self._mesh is None:
            self._x = jnp.asarray(x)
            self._y = jnp.asarray(y)
            return
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        d = self._mesh.shape[self._data_axis]
        if self.batch_size % d != 0:
            raise ValueError(
                f"batch_size {self.batch_size} must divide by the data-axis "
                f"size {d} for the sharded cache")
        # equal shards: x holds this PROCESS's records (the wrapped
        # DistributedDataSet yields the per-process slice), covering
        # d / process_count local shards
        if d % jax.process_count() != 0:
            raise ValueError(
                f"sharded cache needs the data-axis size ({d}) to divide by "
                f"the process count ({jax.process_count()}); lay the data "
                "axis out across processes evenly or skip the cache")
        d_local = d // jax.process_count()
        n_local = (x.shape[0] // d_local) * d_local
        x, y = x[:n_local], y[:n_local]
        if n_local * jax.process_count() < self.batch_size:
            raise ValueError(
                f"{n_local * jax.process_count()} samples cannot fill one "
                f"sharded batch of {self.batch_size} over {d} shards")
        sharding = NamedSharding(self._mesh, P(self._data_axis))
        if jax.process_count() > 1:
            self._x = jax.make_array_from_process_local_data(sharding, x)
            self._y = jax.make_array_from_process_local_data(sharding, y)
        else:
            # straight from the host: each shard goes to its own device
            # (via jnp.asarray the whole set would land on device 0 first)
            self._x = jax.device_put(x, sharding)
            self._y = jax.device_put(y, sharding)

    def _sharded_gather(self):
        """Jitted per-shard gather: local indices pick local rows — no
        cross-device data motion, and the output lands exactly in the
        data-parallel batch sharding."""
        if self._gather_fn is None:
            import jax
            from jax import shard_map
            from jax.sharding import PartitionSpec as P
            ax = self._data_axis

            def gather(xs, ys, il):
                # local shapes: xs (S, ...), il (1, Bs) -> (Bs, ...)
                return xs[il[0]], ys[il[0]]

            self._gather_fn = jax.jit(shard_map(
                gather, mesh=self._mesh,
                in_specs=(P(ax), P(ax), P(ax, None)),
                out_specs=(P(ax), P(ax))))
        return self._gather_fn

    # --------------------------------------------------------------- protocol
    def data(self, train: bool) -> Iterator[MiniBatch]:
        self._materialize()
        import jax.numpy as jnp
        n = int(self._x.shape[0])
        n_batches = n // self.batch_size  # static shapes: drop remainder
        if self._mesh is not None:
            d = self._mesh.shape[self._data_axis]
            bs = self.batch_size // d
            s = n // d
            if train:
                if self._perm is None:
                    self.shuffle()
                lperm, self._perm = self._perm, None  # (d, S) local indices
            else:
                # eval: fixed per-shard round-robin (every record exactly
                # once; global order interleaves shards, unlike the host
                # path — evaluators aggregate, so order is immaterial)
                lperm = np.broadcast_to(np.arange(s, dtype=np.int32),
                                        (d, s))
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            ish = NamedSharding(self._mesh, P(self._data_axis, None))
            if jax.process_count() > 1:
                # each process contributes its own shards' rows (its local
                # RNG generated them; remote rows in lperm are ignored)
                d_local = d // jax.process_count()
                lo = jax.process_index() * d_local
                idx_dev = jax.make_array_from_process_local_data(
                    ish, np.ascontiguousarray(lperm[lo:lo + d_local]))
            else:
                idx_dev = jax.device_put(
                    jnp.asarray(np.ascontiguousarray(lperm)), ish)
            gather = self._sharded_gather()
            for b in range(n // self.batch_size):
                il = idx_dev[:, b * bs:(b + 1) * bs]
                xb, yb = gather(self._x, self._y, il)
                yield MiniBatch(xb, yb)
            return
        if train:
            if self._perm is None:
                self.shuffle()
            perm = self._perm
            self._perm = None  # one permutation per epoch
            idx_dev = jnp.asarray(perm)  # one tiny (N,) int32 upload/epoch
            for b in range(n_batches):
                sl = idx_dev[b * self.batch_size:(b + 1) * self.batch_size]
                yield CachedSliceBatch(self, sl)
        else:
            for b in range(n_batches):
                lo, hi = b * self.batch_size, (b + 1) * self.batch_size
                yield MiniBatch(self._x[lo:hi], self._y[lo:hi])

    def size(self) -> int:
        if self._x is not None:
            return int(self._x.shape[0])
        return self.base.size()

    def shuffle(self) -> None:
        # materialize first: the wrapped chain may change record cardinality
        # (1:0/1:n stages), and a permutation sized from base.size() would
        # silently clamp or truncate gathers
        self._materialize()
        n = int(self._x.shape[0])
        rng = RandomGenerator.RNG()
        if self._mesh is not None:
            # per-shard local permutations (reference semantics: each
            # cached partition reshuffles its OWN indexes,
            # DataSet.scala:292-299); randperm is 1-based -> -1
            d = self._mesh.shape[self._data_axis]
            s = n // d
            self._perm = np.stack(
                [np.asarray(rng.randperm(s) - 1, np.int32)
                 for _ in range(d)])
            return
        # randperm is 1-based (Torch semantics); indices here are 0-based
        self._perm = np.asarray(rng.randperm(n) - 1, np.int32)

    def is_distributed(self) -> bool:
        # routes the Optimizer factory: a cache over a distributed base (or
        # an injected mesh) trains through DistriOptimizer
        return self._mesh is not None or self.base.is_distributed()

    def transform(self, transformer):
        raise TypeError(
            "DeviceCachedDataSet is terminal: apply transformers to the "
            "wrapped dataset BEFORE caching (random host augmentations "
            "would be frozen at materialization)")
