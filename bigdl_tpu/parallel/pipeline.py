"""Pipeline parallelism: GPipe + interleaved (circular) schedules over the
mesh ``pipe`` axis.

New capability — the reference has none (SURVEY §2.5: "Pipeline parallelism:
ABSENT"). TPU-native design:

- A deep model is expressed as ``PipelineStack``: ``depth`` repetitions of a
  homogeneous block whose parameters are STACKED on a leading layer axis
  (leaves shaped (depth, ...)). Single-device forward is a ``lax.scan`` over
  the layer axis (this is also the memory-friendly way to run deep
  transformers on one chip — one compiled block body, not ``depth`` inlined
  copies). Blocks MAY carry buffers (BatchNorm running stats): buffers are
  stacked per layer and updated microbatch-sequentially, the same semantics
  gradient-accumulation frameworks use.
- Under pipeline parallelism the layer axis is simply SHARDED over the mesh
  ``pipe`` axis (spec ``P('pipe', ...)``): each device owns ``depth/P``
  stacked layers. ``gpipe_loss_fn`` runs the schedule inside ``shard_map``:
  microbatches enter stage 0 and march stage-to-stage via ``lax.ppermute``
  (neighbour ICI hops). ``jax.grad`` through the schedule IS the backward
  pipeline — ppermute's transpose reverses the ring.
- The schedule loop is a ``lax.scan`` over time steps (NOT a Python-unrolled
  loop): trace/compile time is flat in the microbatch count, so deep
  pipelines can run n_micro >> stages, where the GPipe bubble
  (P-1)/(M+P-1) vanishes.
- ``interleave=V`` selects the circular schedule: each device owns V
  round-robin layer chunks (layer l lives on device l % P), a microbatch
  rides the ring V times, and the bubble shrinks V-fold to
  (P-1)/(V*M+P-1) at the cost of buffering up to M-P in-flight microbatch
  activations on stage 0. Requires n_micro >= P. Use
  ``circular_permutation`` to pre-permute the stacked layer axis so the
  plain ``P('pipe')`` sharding hands each device its V chunks.

The stacked layout means pipeline parallelism here is a *sharding choice*
over the same arrays as single-chip execution — switching P (or V) requires
no re-partitioning of the model definition, matching the framework's "one
mesh, many layouts" design.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.lax import axis_size, pcast

from bigdl_tpu.nn.module import Module, functional_apply
from bigdl_tpu.parallel.mesh import PIPELINE_AXIS


class PipelineStack(Module):
    """``depth`` copies of ``block`` with parameters stacked on axis 0.

    ``block_factory()`` must build a block whose output shape equals its
    input shape (transformer blocks, residual conv blocks). Blocks may
    carry buffers (BatchNorm running stats): buffer leaves are stacked per
    layer like parameters and updated as each microbatch passes.
    """

    def __init__(self, block_factory: Callable[[], Module], depth: int):
        super().__init__()
        self.depth = depth
        self.block = block_factory()
        per_layer, per_layer_buf = [], []
        for _ in range(depth):
            b = block_factory()
            per_layer.append(b.parameter_tree())
            per_layer_buf.append(b.buffer_tree())
        self._stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *per_layer)
        self._stacked_buf = (jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *per_layer_buf)
            if per_layer_buf[0] else {})

    # The stacked trees ARE this module's parameters/buffers.
    def parameter_tree(self) -> Dict[str, Any]:
        return self._stacked

    def load_parameter_tree(self, tree) -> None:
        self._stacked = tree

    def buffer_tree(self) -> Dict[str, Any]:
        return self._stacked_buf

    def load_buffer_tree(self, tree) -> None:
        self._stacked_buf = tree

    @property
    def has_buffers(self) -> bool:
        return bool(self._stacked_buf)

    def scan_apply(self, params, x, training: bool = False, buffers=None):
        """Sequential forward: scan over the layer axis. Returns ``out`` or
        ``(out, new_buffers)`` when the stack carries buffers."""
        block = self.block
        with_buf = buffers is not None and self.has_buffers

        def body(h, xs):
            if with_buf:
                layer_params, layer_buf = xs
                out, new_buf = functional_apply(block, layer_params,
                                                layer_buf, h,
                                                training=training)
                return out, new_buf
            out, _ = functional_apply(block, xs, {}, h, training=training)
            return out, None

        xs = (params, buffers) if with_buf else params
        out, ys = lax.scan(body, x, xs)
        if with_buf:
            return out, ys
        return out

    def update_output(self, input):
        if self.has_buffers:
            out, new_buf = self.scan_apply(self.parameter_tree(), input,
                                           training=self.training,
                                           buffers=self.buffer_tree())
            if self.training:
                self._stacked_buf = new_buf
            return out
        return self.scan_apply(self.parameter_tree(), input,
                               training=self.training)

    def __repr__(self):
        return f"PipelineStack(depth={self.depth}, block={self.block!r})"


def pipeline_spec_tree(stack: PipelineStack, axis: str = PIPELINE_AXIS):
    """PartitionSpecs sharding the stacked layer axis over ``pipe``."""
    from jax.sharding import PartitionSpec as P
    return jax.tree_util.tree_map(
        lambda leaf: P(axis, *([None] * (leaf.ndim - 1))),
        stack.parameter_tree())


def circular_permutation(depth: int, p: int, interleave: int) -> np.ndarray:
    """Layer permutation for the circular schedule: the plain contiguous
    ``P('pipe')`` shard of device ``d`` then contains its V round-robin
    chunks in chunk order — chunk ``v`` of device ``d`` holds true layers
    ``[(v*p + d)*c, (v*p + d + 1)*c)`` with ``c = depth / (p*V)``."""
    assert depth % (p * interleave) == 0, (depth, p, interleave)
    c = depth // (p * interleave)
    return np.asarray([(v * p + d) * c + j
                       for d in range(p)
                       for v in range(interleave)
                       for j in range(c)], dtype=np.int32)


def schedule_length(n_micro: int, p: int, interleave: int = 1) -> int:
    """Time steps of the schedule: bubble fraction = (P-1)/length."""
    return n_micro * interleave + p - 1


def gpipe_apply(stack: PipelineStack, local_params, x,
                n_micro: int, axis_name: str = PIPELINE_AXIS,
                training: bool = False, remat: bool = False,
                local_buffers=None):
    """GPipe forward INSIDE shard_map.

    local_params: this stage's slice, leaves (depth/P, ...).
    x: full batch (replicated over the pipe axis); batch size must divide
    by ``n_micro``. Returns the model output (replicated over the axis), or
    ``(output, new_local_buffers)`` when buffers are passed.
    ``remat=True`` recomputes each stage's internals in the backward
    (``jax.checkpoint``), bounding live activation memory at one microbatch
    boundary per schedule slot — the standard deep-pipeline recipe.

    The time loop is a ``lax.scan``: one compiled step body regardless of
    ``n_micro`` (compile time flat in microbatch count).
    """
    p = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b = x.shape[0]
    assert b % n_micro == 0, f"batch {b} must divide into {n_micro} microbatches"
    mbs = x.reshape(n_micro, b // n_micro, *x.shape[1:])
    with_buf = local_buffers is not None and stack.has_buffers

    def stage_fn(h, bufs):
        if with_buf:
            return stack.scan_apply(local_params, h, training=training,
                                    buffers=bufs)
        return stack.scan_apply(local_params, h, training=training), bufs

    if remat:
        stage_fn = jax.checkpoint(stage_fn)

    perm = [(i, (i + 1) % p) for i in range(p)]
    state0 = pcast(jnp.zeros_like(mbs[0]), (axis_name,), to="varying")
    out_buf0 = pcast(jnp.zeros_like(mbs), (axis_name,), to="varying")
    is_first = (idx == 0)
    is_last = (idx == p - 1)

    def step(carry, t):
        state, out_buf, bufs = carry
        feed = lax.dynamic_index_in_dim(
            mbs, jnp.minimum(t, n_micro - 1), 0, keepdims=False)
        inp = jnp.where(is_first & (t < n_micro), feed, state)
        out, new_bufs = stage_fn(inp, bufs)
        if with_buf:
            # Idle (bubble) steps see garbage activations: a stage's
            # buffers may only advance while it holds a real microbatch.
            active = (t >= idx) & (t < idx + n_micro)
            bufs = jax.tree_util.tree_map(
                lambda nb, ob: jnp.where(active, nb, ob), new_bufs, bufs)
        w = t - (p - 1)
        upd = lax.dynamic_update_index_in_dim(out_buf, out,
                                              jnp.maximum(w, 0), 0)
        out_buf = jnp.where(is_last & (w >= 0), upd, out_buf)
        state = lax.ppermute(out, axis_name, perm)
        return (state, out_buf, bufs), None

    (_, out_buf, bufs), _ = lax.scan(
        step, (state0, out_buf0, local_buffers),
        jnp.arange(schedule_length(n_micro, p)))

    # Only the last stage holds real outputs; psum replicates them (its
    # transpose broadcasts the output cotangent back to the last stage).
    out_buf = lax.psum(out_buf, axis_name)
    out = out_buf.reshape(b, *out_buf.shape[2:])
    if with_buf:
        return out, bufs
    return out


def circular_apply(stack: PipelineStack, local_params, x, n_micro: int,
                   interleave: int, axis_name: str = PIPELINE_AXIS,
                   training: bool = False, remat: bool = False):
    """Interleaved (circular) pipeline forward INSIDE shard_map.

    Device ``d`` holds ``interleave`` (=V) round-robin layer chunks (the
    ``circular_permutation`` layout); items ride the ring V times in a
    chunk-major conveyor (all microbatches of chunk v, then chunk v+1),
    so the steady-state bubble is ``(P-1)/(V*M+P-1)`` — V times smaller
    than GPipe. Requires ``n_micro >= P`` (the wrap-around latency) and a
    buffer of ``M-P+1`` in-flight activations. Buffered stacks are not
    supported here (use the GPipe schedule for BatchNorm stacks).
    """
    assert not stack.has_buffers, \
        "circular schedule supports buffer-free stacks only"
    p = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    v = interleave
    b = x.shape[0]
    m = n_micro
    assert b % m == 0, f"batch {b} must divide into {m} microbatches"
    assert m >= p, f"circular schedule needs n_micro ({m}) >= stages ({p})"
    mbs = x.reshape(m, b // m, *x.shape[1:])

    local_depth = jax.tree_util.tree_leaves(local_params)[0].shape[0]
    assert local_depth % v == 0, (local_depth, v)
    lc = local_depth // v

    def chunk_fn(vv, h):
        chunk_params = jax.tree_util.tree_map(
            lambda leaf: lax.dynamic_slice_in_dim(leaf, vv * lc, lc, 0),
            local_params)
        return stack.scan_apply(chunk_params, h, training=training)

    if remat:
        chunk_fn = jax.checkpoint(chunk_fn)

    perm = [(i, (i + 1) % p) for i in range(p)]
    delay = m - p  # steps a wrapped activation waits before stage 0 reuses it
    state0 = pcast(jnp.zeros_like(mbs[0]), (axis_name,), to="varying")
    fifo0 = pcast(
        jnp.zeros((delay + 1,) + mbs.shape[1:], mbs.dtype),
        (axis_name,), to="varying")
    out_buf0 = pcast(jnp.zeros_like(mbs), (axis_name,), to="varying")
    is_first = (idx == 0)
    is_last = (idx == p - 1)

    def step(carry, t):
        state, fifo, out_buf = carry
        # Item s = v*M + m_i on device d at time t = s + d.
        s = jnp.clip(t - idx, 0, v * m - 1)
        vv, mi = s // m, s % m
        fresh = lax.dynamic_index_in_dim(mbs, mi, 0, keepdims=False)
        # Stage 0's chunk-v>0 input: the wrap-around delivery of item
        # s - M (written to the fifo at step s - M + P - 1) is consumed
        # ``delay`` steps later — which is exactly when its slot comes up
        # for rewrite, so read slot t BEFORE this step's write below.
        recycled = lax.dynamic_index_in_dim(
            fifo, t % (delay + 1), 0, keepdims=False)
        inp = jnp.where(is_first, jnp.where(vv == 0, fresh, recycled), state)
        out = chunk_fn(vv, inp)
        # Last chunk done on last device: record microbatch output.
        w = jnp.maximum(s - (v - 1) * m, 0)
        upd = lax.dynamic_update_index_in_dim(out_buf, out, w, 0)
        out_buf = jnp.where(is_last & (vv == v - 1) & (t - idx >= 0),
                            upd, out_buf)
        nxt = lax.ppermute(out, axis_name, perm)
        fifo = lax.dynamic_update_index_in_dim(fifo, nxt,
                                               t % (delay + 1), 0)
        return (nxt, fifo, out_buf), None

    (_, _, out_buf), _ = lax.scan(
        step, (state0, fifo0, out_buf0),
        jnp.arange(schedule_length(m, p, v)))
    out_buf = lax.psum(out_buf, axis_name)
    return out_buf.reshape(b, *out_buf.shape[2:])


def gpipe_loss_fn(stack: PipelineStack, criterion, mesh,
                  n_micro: int, axis_name: str = PIPELINE_AXIS,
                  head: Optional[Callable] = None, remat: bool = False,
                  interleave: int = 1,
                  data_axis: Optional[str] = None):
    """(stacked_params, head_params, x, labels) -> scalar loss, jittable;
    with a buffered stack the signature gains a buffers argument and the
    return becomes ``(loss, new_buffers)``.

    Wraps the schedule in shard_map over ``mesh``; ``head`` is an optional
    pure fn (head_params, features) -> logits applied after the stack
    (replicated — run it on every stage; it is tiny relative to the stack).
    ``interleave=V > 1`` selects the circular schedule (pass parameters
    pre-permuted with ``circular_permutation``).

    ``data_axis``: dp x pp composition — the batch shards over this mesh
    axis (each data group runs an independent pipeline over its slice)
    and the per-group mean losses ``pmean`` into the global loss, so
    ``jax.grad`` yields dp-averaged gradients exactly like
    DistriOptimizer's allreduce plane.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    p_specs = pipeline_spec_tree(stack, axis_name)
    x_spec = P(data_axis) if data_axis else P()

    if stack.has_buffers:
        assert interleave == 1, \
            "circular schedule supports buffer-free stacks only"
        assert data_axis is None, (
            "buffered stacks under dp would need cross-group stat "
            "merging; use buffer-free blocks with data_axis")
        b_specs = jax.tree_util.tree_map(
            lambda leaf: P(axis_name, *([None] * (leaf.ndim - 1))),
            stack.buffer_tree())

        def local_fn_buf(stacked, bufs, head_params, x, labels):
            feats, new_bufs = gpipe_apply(stack, stacked, x, n_micro,
                                          axis_name, training=True,
                                          remat=remat, local_buffers=bufs)
            logits = head(head_params, feats) if head is not None else feats
            loss = criterion.apply(logits, labels).astype(jnp.float32)
            return loss, new_bufs

        return shard_map(
            local_fn_buf, mesh=mesh,
            in_specs=(p_specs, b_specs, P(), P(), P()),
            out_specs=(P(), b_specs),
            check_vma=False)

    def local_fn(stacked, head_params, x, labels):
        if interleave > 1:
            feats = circular_apply(stack, stacked, x, n_micro, interleave,
                                   axis_name, training=True, remat=remat)
        else:
            feats = gpipe_apply(stack, stacked, x, n_micro, axis_name,
                                training=True, remat=remat)
        logits = head(head_params, feats) if head is not None else feats
        loss = criterion.apply(logits, labels).astype(jnp.float32)
        if data_axis:
            loss = lax.pmean(loss, data_axis)
        return loss

    return shard_map(
        local_fn, mesh=mesh,
        in_specs=(p_specs, P(), x_spec, x_spec),
        out_specs=P(),
        check_vma=False)


# ---------------------------------------------------------------------------
# Heterogeneous stage-list pipelining (round 4)
# ---------------------------------------------------------------------------

class StagePipeline:
    """GPipe over a LIST of arbitrary, shape-heterogeneous stages — the API
    that pipelines a REAL model end-to-end: ``[embedding+blocks, blocks,
    blocks+norm+head]`` for an LM, or ResNet-50's four stages (each with a
    different activation shape).

    ``PipelineStack`` requires homogeneous blocks because its schedule
    scans one block body over a stacked layer axis and ships one
    fixed-shape activation around the ring. Heterogeneity breaks both, so
    this class restores the two invariants XLA needs by construction:

    - per-device COMPUTE: each device runs its own stage through
      ``lax.switch`` on the stage index — one compiled program containing
      every stage body, each device executing only its own at runtime
      (SPMD programs must be identical; the switch makes them so);
    - fixed-shape TRANSPORT: per-stage parameters ravel into one
      (P, max_param_len) array (sharded over ``pipe`` — each device holds
      only its own stage's weights, preserving pipeline memory scaling),
      and inter-stage activations travel as a flat conduit padded to the
      LARGEST boundary activation, unpacked per stage to its static shape
      inside the switch branch.

    Stage modules may carry CONSTANT buffers (a PositionalEncoding table)
    — they ride along as compile-time constants — but not step-MUTABLE
    ones (BatchNorm running stats, decode caches): bubble steps would
    corrupt them, so mutation is detected at construction (one real
    forward per stage on the sample microbatch, before/after comparison)
    and rejected; use norm-free/LayerNorm stages, or the homogeneous
    ``PipelineStack`` which threads buffers. Shapes are discovered on the
    same probe forward, so stages may change the activation shape
    arbitrarily (downsampling convs, vocab heads). ``jax.grad`` through
    the schedule is the backward pipeline, exactly as for
    ``PipelineStack``.
    """

    def __init__(self, stages, sample_microbatch):
        if len(stages) < 2:
            raise ValueError("need at least 2 stages to pipeline")
        self.stages = list(stages)
        p = len(stages)
        from jax.flatten_util import ravel_pytree
        flats, self._unravels, lens = [], [], []
        for st in stages:
            flat, unravel = ravel_pytree(st.parameter_tree())
            flats.append(flat)
            self._unravels.append(unravel)
            lens.append(flat.shape[0])
        self._param_lens = lens
        self.max_param_len = max(lens)
        # HOST-side stack (numpy): the full (P, max_len) array must never
        # materialise on one device — pipelining exists precisely for
        # models that exceed one chip's HBM. The caller device_puts it
        # with pipe.spec(), so each device only ever receives its row.
        self._stacked = np.stack([
            np.pad(np.asarray(f), (0, self.max_param_len - f.shape[0]))
            for f in flats])

        # probe forward per stage: discovers boundary shapes AND proves the
        # stage's buffers are step-constant (mutable state cannot survive
        # the schedule's bubble steps)
        x = jnp.asarray(sample_microbatch)
        self._in_shapes, self._in_dtypes, self._const_bufs = [], [], []
        for i, st in enumerate(stages):
            self._in_shapes.append(tuple(x.shape))
            self._in_dtypes.append(x.dtype)
            bufs = st.buffer_tree()
            self._const_bufs.append(bufs)
            x, new_bufs = functional_apply(st, st.parameter_tree(), bufs, x,
                                           training=True)
            changed = [
                k for k, (a, b) in enumerate(zip(
                    jax.tree_util.tree_leaves(bufs),
                    jax.tree_util.tree_leaves(new_bufs)))
                if not np.allclose(np.asarray(a), np.asarray(b))]
            if changed:
                raise ValueError(
                    f"stage {i} mutates buffers during forward (BatchNorm "
                    "running stats?); StagePipeline needs step-constant "
                    "stages — use LayerNorm/GroupNorm, or the homogeneous "
                    "PipelineStack which threads buffers")
        self.out_shape, self.out_dtype = tuple(x.shape), x.dtype
        # the conduit carries stage-boundary activations AND stage 0's
        # fresh feed (same buffer via the is_first select), so size to the
        # largest of all of them
        sizes = [int(np.prod(s)) for s in self._in_shapes]
        sizes.append(int(np.prod(self.out_shape)))
        self.conduit_len = max(sizes)

    @property
    def n_stages(self) -> int:
        return len(self.stages)

    def parameter_tree(self):
        """(P, max_param_len) — shard row-wise over the ``pipe`` axis."""
        return self._stacked

    def spec(self, axis: str = PIPELINE_AXIS):
        from jax.sharding import PartitionSpec as P
        return P(axis, None)

    def unstack_parameter_trees(self, stacked):
        """Inverse of the stacked layout: per-stage pytrees (for moving
        trained weights back into the stage modules / checkpoints)."""
        return [self._unravels[i](stacked[i, :self._param_lens[i]])
                for i in range(len(self.stages))]

    def sequential_apply(self, stacked, x, training: bool = True):
        """Reference forward (no pipelining): the exact math the schedule
        must reproduce; used by differential tests and single-device runs."""
        h = x
        for i, st in enumerate(self.stages):
            params = self._unravels[i](stacked[i, :self._param_lens[i]])
            h, _ = functional_apply(st, params, self._const_bufs[i], h,
                                    training=training)
        return h

    def _branch(self, i, training: bool):
        """Stage i body: flat conduit in -> flat conduit out."""
        st = self.stages[i]
        in_shape, in_dtype = self._in_shapes[i], self._in_dtypes[i]
        n_in = int(np.prod(in_shape))
        bufs = self._const_bufs[i]  # step-constant, proven at __init__

        def body(flat_params, conduit):
            params = self._unravels[i](flat_params[:self._param_lens[i]])
            h = conduit[:n_in].reshape(in_shape).astype(in_dtype)
            out, _ = functional_apply(st, params, bufs, h,
                                      training=training)
            flat = out.astype(jnp.float32).reshape(-1)
            return jnp.pad(flat, (0, self.conduit_len - flat.shape[0]))

        return body

    def pipeline_apply(self, local_stacked, x, n_micro: int,
                       axis_name: str = PIPELINE_AXIS,
                       remat: bool = False, training: bool = True):
        """GPipe schedule INSIDE shard_map: microbatches enter stage 0,
        march stage-to-stage via ``lax.ppermute`` in the flat conduit, and
        the last stage's outputs are psum-replicated (transpose: the
        output cotangent re-enters the backward ring at the last stage)."""
        p = axis_size(axis_name)
        assert p == len(self.stages), (
            f"mesh '{axis_name}' axis ({p}) must equal the stage count "
            f"({len(self.stages)})")
        idx = lax.axis_index(axis_name)
        b = x.shape[0]
        assert b % n_micro == 0, (b, n_micro)
        mb = self._in_shapes[0][0]
        assert b // n_micro == mb, (
            f"microbatch {b}//{n_micro}={b // n_micro} != sample_microbatch "
            f"batch {mb} used at construction (conduit sizes are static)")
        mbs = x.reshape(n_micro, b // n_micro, *x.shape[1:])
        n_in0 = int(np.prod(self._in_shapes[0]))
        out_len = int(np.prod(self.out_shape))

        branches = [self._branch(i, training) for i in range(p)]
        if remat:
            branches = [jax.checkpoint(fn) for fn in branches]

        def compute(flat_params, conduit):
            return lax.switch(idx, branches, flat_params[0], conduit)

        perm = [(i, (i + 1) % p) for i in range(p)]
        state0 = pcast(jnp.zeros((self.conduit_len,), jnp.float32),
                           (axis_name,), to="varying")
        out_buf0 = pcast(
            jnp.zeros((n_micro, out_len), jnp.float32),
            (axis_name,), to="varying")
        is_first = (idx == 0)
        is_last = (idx == p - 1)

        def step(carry, t):
            state, out_buf = carry
            feed = lax.dynamic_index_in_dim(
                mbs, jnp.minimum(t, n_micro - 1), 0,
                keepdims=False).astype(jnp.float32).reshape(-1)
            feed = jnp.pad(feed, (0, self.conduit_len - n_in0))
            inp = jnp.where(is_first & (t < n_micro), feed, state)
            out = compute(local_stacked, inp)
            w = t - (p - 1)
            upd = lax.dynamic_update_index_in_dim(
                out_buf, out[:out_len], jnp.maximum(w, 0), 0)
            out_buf = jnp.where(is_last & (w >= 0), upd, out_buf)
            state = lax.ppermute(out, axis_name, perm)
            return (state, out_buf), None

        (_, out_buf), _ = lax.scan(
            step, (state0, out_buf0),
            jnp.arange(schedule_length(n_micro, p)))
        out_buf = lax.psum(out_buf, axis_name)
        mb = b // n_micro
        return out_buf.reshape(n_micro * mb, *self.out_shape[1:]) \
            .astype(self.out_dtype)


def stage_pipeline_loss_fn(pipe: StagePipeline, criterion, mesh,
                           n_micro: int, axis_name: str = PIPELINE_AXIS,
                           remat: bool = False,
                           data_axis: Optional[str] = None):
    """(stacked_params (P, L), x, labels) -> scalar loss, jittable.

    The heterogeneous counterpart of ``gpipe_loss_fn``: pass
    ``pipe.parameter_tree()`` placed with ``pipe.spec()`` so each device
    holds only its stage's weights. ``data_axis`` composes dp x pp the
    same way (independent pipelines per data group, pmean'd loss)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    x_spec = P(data_axis) if data_axis else P()

    def local_fn(stacked, x, labels):
        feats = pipe.pipeline_apply(stacked, x, n_micro, axis_name,
                                    remat=remat)
        loss = criterion.apply(feats, labels).astype(jnp.float32)
        if data_axis:
            loss = lax.pmean(loss, data_axis)
        return loss

    return shard_map(local_fn, mesh=mesh,
                     in_specs=(pipe.spec(axis_name), x_spec, x_spec),
                     out_specs=P(), check_vma=False)
