"""Distributed synchronous-SGD training over a device mesh
(reference ``optim/DistriOptimizer.scala:669`` + ``parameters/AllReduceParameter.scala:62``).

The reference runs, per iteration, two Spark jobs and three BlockManager
block exchanges: fetch weight slices → local fwd/bwd → publish fp16 gradient
slices → owners aggregate + update their slice → republish. On TPU the entire
iteration is ONE jitted SPMD program; the exchanges become XLA collectives
riding ICI:

- ``sync_mode="allreduce"`` — replicated parameters, batch sharded over the
  ``data`` axis; XLA's SPMD partitioner inserts the gradient psum. The two
  intra-node tiers of the reference (executor slice exchange + per-core
  replica reduce, ``DistriOptimizer.scala:112-115,229-246``) collapse into
  this single psum.

- ``sync_mode="sharded"`` — the AllReduceParameter slice-ownership model,
  TPU-native (≙ ZeRO-1): the flat parameter vector is conceptually cut into
  P slices; gradients ``psum_scatter`` so each device reduces only its own
  slice, the optimizer updates that slice (optimizer state stays sharded —
  P× less optimizer memory), and ``all_gather`` republishes the weights.
  This is bit-for-bit the reference's protocol with BlockManager fetches
  replaced by reduce-scatter/all-gather.

bf16 gradient compression (reference ``FP16CompressedTensor``: fp32 truncated
to its top 16 bits == bfloat16) maps to casting the collective payload to
``jnp.bfloat16`` — ``compress_gradients=True``.

BatchNorm note: in allreduce mode batch-stat means over the sharded batch are
computed globally by XLA → synchronized BN across replicas (an upgrade over
the reference's per-replica stats); in sharded mode new buffers are pmean'd.

Multi-host: when ``Engine.init`` joined a jax.distributed topology (env
``BIGDL_COORDINATOR_ADDRESS``/..., or TPU-pod auto-detect), the same jitted
step spans every host's chips. Per-process ingest (``DistributedDataSet``
record slices ≙ executor-pinned partitions) feeds
``jax.make_array_from_process_local_data``; state is committed to the global
mesh by ``_place_state``; checkpoints gather sharded leaves and write on
process 0 only; validation merges per-host (numerator, count) pairs with one
allgather. Verified by ``tests/test_multihost.py`` (2 real processes, gloo).
"""

from __future__ import annotations

import logging
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.nn.module import functional_apply
from bigdl_tpu.optim.optimizer import (LocalOptimizer, Optimizer,
                                       _regularizer_pairs, _reg_loss,
                                       clipped_update, make_grad_clipper,
                                       make_training_loss_fn)
from bigdl_tpu.parallel.mesh import DATA_AXIS, TENSOR_AXIS, MeshTopology
from bigdl_tpu.telemetry.profiling import tracked_jit

logger = logging.getLogger("bigdl_tpu.optim")


class DistriOptimizer(LocalOptimizer):
    """Mesh data-parallel optimizer (reference ``DistriOptimizer``)."""

    def __init__(self, model, dataset, criterion,
                 topology: Optional[MeshTopology] = None,
                 sync_mode: str = "allreduce",
                 compress_gradients: bool = False,
                 **kwargs):
        super().__init__(model, dataset, criterion, **kwargs)
        self.topology = topology or MeshTopology.data_parallel()
        self.sync_mode = sync_mode
        self.compress_gradients = compress_gradients
        if topology and any(
                topology.sizes.get(ax, 1) > 1 for ax in ("tensor", "expert")):
            # fsdp composes with tensor parallelism (weight shards carry
            # both axes); the ZeRO-1 flat vector and expert stacking are
            # data-axis-only layouts
            if sync_mode == "sharded" or (
                    sync_mode == "fsdp"
                    and topology.sizes.get("expert", 1) > 1):
                raise ValueError(f"sync_mode={sync_mode!r} does not "
                                 "compose with this topology; combine "
                                 "expert parallelism with "
                                 "sync_mode='allreduce' (fsdp x tensor "
                                 "is supported)")
        self.mesh: Mesh = self.topology.build()
        self._n_data = self.mesh.shape.get(DATA_AXIS, 1)
        self._n_tensor = self.mesh.shape.get(TENSOR_AXIS, 1)
        batch_spec = P(DATA_AXIS) if DATA_AXIS in self.mesh.shape else P()
        self._batch_sharding = NamedSharding(self.mesh, batch_spec)
        self._replicated = NamedSharding(self.mesh, P())
        # a DeviceCachedDataSet shards its cache over our data axis
        # (per-partition cache ≙ reference CachedDistriDataSet)
        from bigdl_tpu.dataset.device_cache import DeviceCachedDataSet
        if isinstance(dataset, DeviceCachedDataSet):
            dataset.set_mesh(self.mesh, DATA_AXIS)

    def _telemetry_mode(self) -> str:
        """Distributed step breakdowns scrape as their own series:
        ``bigdl_train_*{mode="mesh-allreduce|sharded|fsdp"}`` next to the
        local loop's ``mode="local"`` (docs/OBSERVABILITY.md)."""
        return f"mesh-{self.sync_mode}"

    def _mesh_descriptor(self):
        """RESUME-marker topology record: elastic-resume detection compares
        the saving run's process/device counts against the restarting
        run's, and the mesh shape documents what the snapshot's shard
        layout meant (docs/RESILIENCE.md)."""
        return {"process_count": int(jax.process_count()),
                "device_count": int(jax.device_count()),
                "mesh_shape": {ax: int(n)
                               for ax, n in self.mesh.shape.items()},
                "sync_mode": self.sync_mode}

    # ------------------------------------------------------------- placement
    def _place_batch(self, batch):
        """Commit one batch onto the mesh's data axis.

        Single-host: the pipeline's batch IS the global batch — device_put
        shards it. Multi-host: the pipeline yields this process's LOCAL
        records only (``DistributedDataSet`` per-process slice ≙ the
        reference's executor-pinned partitions, ``CachedDistriDataSet``);
        ``jax.make_array_from_process_local_data`` assembles the global
        array without any host ever holding the full batch."""
        data = batch.data
        if (isinstance(data, jax.Array) and hasattr(data, "sharding")
                and isinstance(data.sharding, NamedSharding)
                and data.sharding.mesh is self.mesh):
            # sharded-cache batches arrive already placed on this mesh
            # (shard_map gather output) — re-placing would force a gather
            # of non-addressable shards under multi-host
            return data, batch.labels
        if jax.process_count() > 1:
            data = jax.make_array_from_process_local_data(
                self._batch_sharding, np.asarray(batch.data))
            labels = jax.make_array_from_process_local_data(
                self._batch_sharding, np.asarray(batch.labels))
            return data, labels
        data = jax.device_put(jnp.asarray(batch.data), self._batch_sharding)
        labels = jax.device_put(jnp.asarray(batch.labels), self._batch_sharding)
        return data, labels

    def _place_state(self, params, buffers, opt_state):
        """Commit training state onto the mesh (multi-host: host-local values
        become global arrays; required before jit sees cross-process
        shardings)."""
        if jax.process_count() <= 1:
            return params, buffers, opt_state
        rep = self._replicated

        def put_rep(x):
            return jax.device_put(jnp.asarray(x), rep)

        n_params = sum(int(np.size(l))
                       for l in jax.tree_util.tree_leaves(params))
        full = n_params + ((-n_params) % self._n_data)
        params = jax.tree_util.tree_map(put_rep, params)
        buffers = jax.tree_util.tree_map(put_rep, buffers)
        if self.sync_mode != "sharded":
            opt_state = jax.tree_util.tree_map(put_rep, opt_state)
        else:
            # slice-shaped vector state lives over the data axis (ZeRO-1);
            # scalar counters are replicated — same rule as _init_opt_state,
            # applied to full-length (possibly checkpoint-resumed) leaves.
            sliced = NamedSharding(self.mesh, P(DATA_AXIS))

            def put_opt(x):
                x = jnp.asarray(x)
                if x.ndim >= 1 and x.shape[0] == full:
                    return jax.device_put(x, sliced)
                return put_rep(x)

            opt_state = jax.tree_util.tree_map(put_opt, opt_state)
        return params, buffers, opt_state

    @staticmethod
    def _fetch_host(x):
        """Global array -> host value (multi-host safe): replicated arrays
        read locally, axis-sharded ones gather via a process allgather."""
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            if not x.is_fully_replicated:
                from jax.experimental import multihost_utils
                return multihost_utils.process_allgather(x, tiled=True)
        return np.asarray(x)

    def _save_checkpoint(self, params, buffers, opt_state, driver_state):
        if self.checkpoint_path is None:
            return
        if getattr(self, "_ckpt_sharded", False):
            if self.sync_mode == "sharded":
                raise ValueError(
                    "set_checkpoint(sharded=True) is not supported with "
                    "sync_mode='sharded' (ZeRO-1 state is device-count-"
                    "shaped; its restore cannot reshard) — use 'fsdp' or "
                    "'allreduce'")
            # every process writes its own shards; no gather at all
            super()._save_checkpoint(params, buffers, opt_state,
                                     driver_state)
            return
        if jax.process_count() > 1:
            fetch = lambda t: jax.tree_util.tree_map(self._fetch_host, t)
            # every process participates in the gather; only the 'driver'
            # writes (reference: checkpoint written by the Spark driver)
            params, buffers, opt_state = (fetch(params), fetch(buffers),
                                          fetch(opt_state))
            if jax.process_index() != 0:
                return
        super()._save_checkpoint(params, buffers, opt_state, driver_state)

    def _resume_shardings(self, params_tpl, buffers_tpl):
        """Sharded-checkpoint restore targets for THIS run's mesh — which
        may differ from the saving run's (the resharding-restore contract):
        fsdp reshards params+state onto its specs; allreduce replicates.
        sync_mode='sharded' (ZeRO-1) keeps flat padded state whose length
        depends on the device count — unsupported for cross-mesh restore,
        use the gathered checkpoint there."""
        if self.sync_mode == "sharded":
            raise ValueError(
                "sharded checkpoints cannot restore into sync_mode="
                "'sharded' (ZeRO-1 flat state is device-count-shaped); "
                "use sync_mode='fsdp' or 'allreduce', or a plain "
                "(gathered) checkpoint")
        rep = self._replicated
        state_tpl = jax.eval_shape(self.optim_method.init_state, params_tpl)
        if self.sync_mode == "fsdp":
            from bigdl_tpu.parallel.fsdp import fsdp_param_specs, named_tree
            from bigdl_tpu.parallel.tensor_parallel import opt_state_specs
            p_specs = fsdp_param_specs(
                params_tpl, self._n_data,
                base_specs=self._tp_base_specs(self.model))
            p_sh = named_tree(self.mesh, p_specs)
            s_sh = named_tree(self.mesh, opt_state_specs(
                state_tpl, params_tpl, p_specs))
            b_sh = jax.tree_util.tree_map(lambda _: rep, buffers_tpl)
            return p_sh, b_sh, s_sh
        rep_of = lambda tpl: jax.tree_util.tree_map(lambda _: rep, tpl)
        return rep_of(params_tpl), rep_of(buffers_tpl), rep_of(state_tpl)

    def _run_validation(self, params, buffers, fwd):
        """Multi-host: each process runs forward over ITS shard of the
        validation set (the dataset must be distributed so records split by
        process), then per-method (numerator, count) pairs merge via one
        allgather — the TPU-native form of ``ValidationResult.+`` reduce
        over executors (``optim/Evaluator.scala:48-73``)."""
        if jax.process_count() <= 1:
            return super()._run_validation(params, buffers, fwd)
        from bigdl_tpu.dataset.device_cache import DeviceCachedDataSet
        if (isinstance(self.validation_dataset, DeviceCachedDataSet)
                and self.validation_dataset._mesh is not None):
            # the sharded cache yields GLOBAL arrays; this path evaluates
            # host-locally per process and allgather-merges, so it needs a
            # per-process host dataset — mixing the two would crash on
            # non-addressable shards (or double-count every record)
            raise ValueError(
                "multi-host validation needs a host-path distributed "
                "dataset (per-process record slices), not a sharded "
                "DeviceCachedDataSet; pass the un-cached pipeline to "
                "set_validation")
        from jax.experimental import multihost_utils
        from bigdl_tpu.optim.evaluator import evaluate_batches

        params_h = jax.tree_util.tree_map(
            self._fetch_host, self._finalize_params(params))
        buffers_h = jax.tree_util.tree_map(self._fetch_host, buffers)
        if getattr(self, "_local_eval_fwd", None) is None:
            model = self.model

            def local_fwd(p, b, x):
                out, _ = functional_apply(model, p, b, x, training=False)
                return out

            self._local_eval_fwd = tracked_jit(local_fwd,
                                               site="eval.forward")
        results, count = evaluate_batches(
            self._local_eval_fwd, params_h, buffers_h,
            self.validation_dataset.data(train=False),
            self.validation_methods, cache=self._eval_cache)
        states = np.array(
            [list(r.state()) if r is not None else [0.0, 0.0]
             for r in results] + [[float(count), 0.0]], np.float64)
        summed = multihost_utils.process_allgather(states).sum(axis=0)
        # Rebuild results from the METHOD (identical on every host), not the
        # local result object: a host whose shard was empty must still see
        # the merged value, or driver_state['score'] diverges across hosts
        # and score-triggered stops deadlock the pod.
        merged = [
            m.to_result(num, int(cnt)) if cnt > 0 else None
            for m, (num, cnt) in zip(self.validation_methods, summed[:-1])]
        return merged, int(summed[-1][0])

    def _tp_base_specs(self, model):
        """Tensor-parallel base specs for the fsdp composition (fsdp x tp:
        weight shards carry both mesh axes), or None on a pure data mesh."""
        if self._n_tensor <= 1:
            return None
        from bigdl_tpu.parallel.tensor_parallel import infer_param_specs
        return infer_param_specs(model, axis_size=dict(self.mesh.shape))

    # ------------------------------------------------------------------ step
    def _build_step(self) -> Callable:
        if self.sync_mode == "sharded":
            return self._build_sharded_step()
        if self.sync_mode == "fsdp":
            return self._build_fsdp_step()
        return self._build_allreduce_step()

    def _build_allreduce_step(self) -> Callable:
        model, criterion, optim = self.model, self.criterion, self.optim_method
        reg_pairs = _regularizer_pairs(model)
        compress = self.compress_gradients
        policy = self.precision
        remat = self._remat

        clip = make_grad_clipper(self._grad_clip)

        def step(params, buffers, opt_state, rng, data, labels):
            loss_fn = make_training_loss_fn(
                model, criterion, policy, reg_pairs, remat,
                buffers, rng, data, labels)

            grads, (new_buf, loss) = jax.grad(loss_fn, has_aux=True)(params)
            if compress:
                # bf16 payload ≙ reference FP16CompressedTensor (truncated fp32)
                with jax.named_scope("grad_sync"):
                    grads = jax.tree_util.tree_map(
                        lambda g: g.astype(jnp.bfloat16).astype(g.dtype),
                        grads)
            # clip the GLOBAL (GSPMD-allreduced) gradient, post-compression,
            # so the update sees the same clipped grad on every device
            new_params, new_opt_state = clipped_update(
                optim, clip, grads, opt_state, params)
            return new_params, new_buf, new_opt_state, loss

        rep, bat = self._replicated, self._batch_sharding
        if self._n_tensor > 1 or self.mesh.shape.get("expert", 1) > 1:
            # Tensor/expert parallelism: per-leaf parameter shardings
            # (Megatron column/row rules, MoE expert stacking); GSPMD
            # inserts the activation collectives/all_to_alls. Optimizer
            # state mirrors the param specs.
            from bigdl_tpu.parallel.tensor_parallel import (
                infer_param_specs, opt_state_specs)
            params0 = self.model.parameter_tree()
            p_specs = infer_param_specs(self.model,
                                        axis_size=dict(self.mesh.shape))
            state_tpl = jax.eval_shape(optim.init_state, params0)
            s_specs = opt_state_specs(state_tpl, params0, p_specs)
            named = lambda tree: jax.tree_util.tree_map(
                lambda sp: NamedSharding(self.mesh, sp), tree,
                is_leaf=lambda x: isinstance(x, P))
            p_sh, s_sh = named(p_specs), named(s_specs)
            return tracked_jit(
                step, site="train.step",
                in_shardings=(p_sh, rep, s_sh, rep, bat, bat),
                out_shardings=(p_sh, rep, s_sh, rep),
                donate_argnums=(0, 1, 2))
        return tracked_jit(
            step, site="train.step",
            in_shardings=(rep, rep, rep, rep, bat, bat),
            out_shardings=(rep, rep, rep, rep),
            donate_argnums=(0, 1, 2))

    def _build_fsdp_step(self) -> Callable:
        """ZeRO-3: parameters + optimizer state sharded at rest over the
        data axis (``parallel/fsdp.py``); XLA inserts the per-layer weight
        all-gathers and the gradient reduce-scatter. Subsumes the
        reference's slice-ownership protocol
        (``parameters/AllReduceParameter.scala:62``) with the ownership
        extended to the weights themselves."""
        from bigdl_tpu.parallel.fsdp import fsdp_param_specs, named_tree
        from bigdl_tpu.parallel.tensor_parallel import opt_state_specs

        model, criterion, optim = self.model, self.criterion, self.optim_method
        reg_pairs = _regularizer_pairs(model)
        compress = self.compress_gradients
        policy = self.precision
        remat = self._remat
        clip = make_grad_clipper(self._grad_clip)

        params0 = model.parameter_tree()
        p_specs = fsdp_param_specs(params0, self._n_data,
                                   base_specs=self._tp_base_specs(model))
        state_tpl = jax.eval_shape(optim.init_state, params0)
        s_specs = opt_state_specs(state_tpl, params0, p_specs)
        p_sh = named_tree(self.mesh, p_specs)
        s_sh = named_tree(self.mesh, s_specs)
        self._param_sharding = p_sh

        def step(params, buffers, opt_state, rng, data, labels):
            loss_fn = make_training_loss_fn(
                model, criterion, policy, reg_pairs, remat,
                buffers, rng, data, labels)

            grads, (new_buf, loss) = jax.grad(loss_fn, has_aux=True)(params)
            with jax.named_scope("grad_sync"):
                if compress:
                    grads = jax.tree_util.tree_map(
                        lambda g: g.astype(jnp.bfloat16).astype(g.dtype),
                        grads)
                # constrain grads to the param shardings: the backward's
                # psum lowers to reduce-scatter (each device keeps its
                # shard) instead of all-reduce + slice
                grads = jax.lax.with_sharding_constraint(grads, p_sh)
            new_params, new_opt_state = clipped_update(
                optim, clip, grads, opt_state, params)
            return new_params, new_buf, new_opt_state, loss

        rep, bat = self._replicated, self._batch_sharding
        return tracked_jit(
            step, site="train.step",
            in_shardings=(p_sh, rep, s_sh, rep, bat, bat),
            out_shardings=(p_sh, rep, s_sh, rep),
            donate_argnums=(0, 1, 2))

    def _build_sharded_step(self) -> Callable:
        from jax.flatten_util import ravel_pytree
        from jax import shard_map

        model, criterion, optim = self.model, self.criterion, self.optim_method
        reg_pairs = _regularizer_pairs(model)
        compress = self.compress_gradients
        clip = make_grad_clipper(self._grad_clip)
        mesh, n_dev = self.mesh, self._n_data

        # Flat-parameter geometry (reference AllReduceParameter slice layout).
        params0 = model.parameter_tree()
        flat0, unravel = ravel_pytree(params0)
        n = flat0.shape[0]
        pad = (-n) % n_dev
        chunk = (n + pad) // n_dev
        self._unravel, self._n, self._pad = unravel, n, pad

        # Per-leaf specs for the optimizer state: slice-shaped vector leaves
        # are sharded over the data axis, scalar counters stay replicated.
        opt_template = optim.init_state(jnp.zeros((chunk,), flat0.dtype))
        opt_specs = jax.tree_util.tree_map(
            lambda x: P(DATA_AXIS)
            if (hasattr(x, "ndim") and np.ndim(x) >= 1 and np.shape(x)[0] == chunk)
            else P(),
            opt_template)

        policy = self.precision

        remat = self._remat

        def spmd_step(flat_params, buffers, opt_state, rng, data, labels):
            # flat_params: full replicated flat vector (post all-gather state).
            params = unravel(flat_params[:n])
            loss_fn = make_training_loss_fn(
                model, criterion, policy, reg_pairs, remat,
                buffers, rng, data, labels)

            grads, (new_buf, loss) = jax.grad(loss_fn, has_aux=True)(params)
            with jax.named_scope("grad_sync"):
                flat_grads, _ = ravel_pytree(grads)
                flat_grads = jnp.pad(flat_grads, (0, pad))
                if compress:
                    flat_grads = flat_grads.astype(jnp.bfloat16)
                # reduce-scatter: each device reduces ONLY its own slice (≙
                # aggregrateGradientPartition,
                # AllReduceParameter.scala:172-210)
                grad_slice = jax.lax.psum_scatter(
                    flat_grads, DATA_AXIS, scatter_dimension=0,
                    tiled=True) / n_dev
                grad_slice = grad_slice.astype(jnp.float32)
            rank = jax.lax.axis_index(DATA_AXIS)
            # clip on the slice: the global L2 norm psums the per-slice
            # squared norms (each device owns 1/P of the flat gradient);
            # the mask keeps PAD lanes at zero through the clamp so the
            # norm matches the allreduce path exactly
            lane = rank * chunk + jnp.arange(chunk)
            with jax.named_scope("optim_update"):
                param_slice = jax.lax.dynamic_slice(
                    flat_params, (rank * chunk,), (chunk,))
            new_slice, new_opt_state = clipped_update(
                optim, clip, grad_slice, opt_state, param_slice,
                axis_name=DATA_AXIS,
                valid_mask=(lane < n).astype(jnp.float32))
            # republish slices (≙ sendWeightPartition + getWeights)
            with jax.named_scope("optim_update"):
                new_flat = jax.lax.all_gather(new_slice, DATA_AXIS,
                                              tiled=True)
            with jax.named_scope("grad_sync"):
                new_buf = jax.tree_util.tree_map(
                    lambda b: jax.lax.pmean(b, DATA_AXIS), new_buf)
                loss = jax.lax.pmean(loss, DATA_AXIS)
            return new_flat, new_buf, new_opt_state, loss

        in_specs = (P(), P(), opt_specs, P(), P(DATA_AXIS), P(DATA_AXIS))
        out_specs = (P(), P(), opt_specs, P())
        sharded = shard_map(spmd_step, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
        # explicit shardings, as in the other two modes: the step then
        # compiles once whatever the placement of its first arguments
        from bigdl_tpu.parallel.fsdp import named_tree
        jitted = tracked_jit(sharded, site="train.step",
                             in_shardings=named_tree(mesh, in_specs),
                             out_shardings=named_tree(mesh, out_specs),
                             donate_argnums=(0, 1, 2))

        def step(params, buffers, opt_state, rng, data, labels):
            # params arrives as a pytree on the first call; thereafter flat.
            if not isinstance(params, jax.Array):
                flat, _ = ravel_pytree(params)
                flat = jnp.pad(flat, (0, pad))
                params = jax.device_put(flat, self._replicated)
            new_flat, new_buf, new_opt, loss = jitted(
                params, buffers, opt_state, rng, data, labels)
            return new_flat, new_buf, new_opt, loss

        # surface the flight recorder through the wrapper (the MFU gauge
        # follows .tracked to read cost analysis off what flush() ran)
        step.tracked = jitted

        step.finalize = lambda flat: unravel(flat[:n])  # flat -> pytree
        step.jitted = jitted  # inspectable (HLO contract tests, debugging)
        return step

    def _build_forward(self) -> Callable:
        model = self.model
        unravel = getattr(self, "_unravel", None)
        n = getattr(self, "_n", None)

        def fwd(params, buffers, data):
            if unravel is not None and isinstance(params, jax.Array):
                params = unravel(params[:n])
            out, _ = functional_apply(model, params, buffers, data, training=False)
            return out

        rep, bat = self._replicated, self._batch_sharding
        # fsdp: validation forward keeps the weights sharded too (XLA
        # gathers per layer); _build_step runs first and records the specs
        p_sh = getattr(self, "_param_sharding", rep)
        return tracked_jit(fwd, site="train.forward",
                           in_shardings=(p_sh, rep, bat), out_shardings=bat)

    # ------------------------------------------------------- optimizer state
    def _init_opt_state(self, params):
        if self.sync_mode != "sharded":
            return super()._init_opt_state(params)
        # Per-slice optimizer state: P× less memory (ZeRO-1), sharded layout.
        from jax.flatten_util import ravel_pytree
        flat, _ = ravel_pytree(params)
        n = flat.shape[0]
        pad = (-n) % self._n_data
        chunk = (n + pad) // self._n_data
        slice_proto = jnp.zeros((chunk,), flat.dtype)
        state = self.optim_method.init_state(slice_proto)
        # Broadcast scalar counters, shard vector state over the data axis.

        def place(x):
            x = jnp.asarray(x)
            if x.ndim >= 1 and x.shape[0] == chunk:
                tiled = jnp.tile(x, (self._n_data,) + (1,) * (x.ndim - 1)) \
                    if x.ndim > 1 else jnp.tile(x, self._n_data)
                return jax.device_put(tiled, NamedSharding(self.mesh, P(DATA_AXIS)))
            return jax.device_put(x, self._replicated)

        return jax.tree_util.tree_map(place, state)

    def _finalize_params(self, params):
        if self.sync_mode == "sharded" and isinstance(params, jax.Array):
            return self._unravel(np.asarray(params)[:self._n])
        return params
