"""GPipe pipeline-parallel tests on the 8-device virtual mesh. Oracle is the
same stacked model run sequentially on one device (differential strategy of
``$T/optim/DistriOptimizerSpec`` applied to the new PP capability)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu.nn.module import functional_apply
from bigdl_tpu.parallel.mesh import MeshTopology
from bigdl_tpu.parallel.pipeline import (PipelineStack, gpipe_loss_fn,
                                         pipeline_spec_tree)


def _block():
    return nn.TransformerEncoderLayer(16, 2, 32, pre_norm=True)


def _rand(*shape):
    return jnp.asarray(np.random.randn(*shape).astype(np.float32))


def test_stack_scan_matches_unrolled():
    stack = PipelineStack(_block, depth=4)
    x = _rand(2, 6, 16)
    out_scan = stack.forward(x)
    # unrolled oracle: apply the block 4 times with each layer's params
    params = stack.parameter_tree()
    h = x
    for i in range(4):
        layer = jax.tree_util.tree_map(lambda leaf: leaf[i], params)
        h, _ = functional_apply(stack.block, layer, {}, h, training=False)
    np.testing.assert_allclose(np.asarray(out_scan), np.asarray(h),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_micro", [4, 8])
def test_gpipe_matches_sequential(n_micro):
    mesh = MeshTopology(pipeline=4).build()
    stack = PipelineStack(_block, depth=8)
    crit = nn.MSECriterion()
    x = _rand(8, 6, 16)
    y = _rand(8, 6, 16)

    loss_fn = gpipe_loss_fn(stack, crit, mesh, n_micro=n_micro)
    loss_pp = jax.jit(loss_fn)(stack.parameter_tree(), None, x, y)

    out_seq = stack.forward(x)
    loss_seq = crit.apply(out_seq, y)
    np.testing.assert_allclose(float(loss_pp), float(loss_seq),
                               rtol=1e-5, atol=1e-5)


def test_gpipe_grads_match_sequential():
    mesh = MeshTopology(pipeline=4).build()
    stack = PipelineStack(_block, depth=4)
    crit = nn.MSECriterion()
    x = _rand(4, 5, 16)
    y = _rand(4, 5, 16)
    params = stack.parameter_tree()

    loss_fn = gpipe_loss_fn(stack, crit, mesh, n_micro=4)
    g_pp = jax.jit(jax.grad(lambda p: loss_fn(p, None, x, y)))(params)

    def seq_loss(p):
        out = stack.scan_apply(p, x)
        return crit.apply(out, y)

    g_seq = jax.grad(seq_loss)(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_pp),
                    jax.tree_util.tree_leaves(g_seq)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_gpipe_remat_grads_identical():
    # jax.checkpoint trades FLOPs for memory; gradients must be unchanged
    mesh = MeshTopology(pipeline=4).build()
    stack = PipelineStack(_block, depth=4)
    crit = nn.MSECriterion()
    x, y = _rand(4, 5, 16), _rand(4, 5, 16)
    params = stack.parameter_tree()
    g_plain = jax.jit(jax.grad(lambda p: gpipe_loss_fn(
        stack, crit, mesh, n_micro=4)(p, None, x, y)))(params)
    g_remat = jax.jit(jax.grad(lambda p: gpipe_loss_fn(
        stack, crit, mesh, n_micro=4, remat=True)(p, None, x, y)))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_plain),
                    jax.tree_util.tree_leaves(g_remat)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_gpipe_with_head_and_sharded_params():
    # Train-shaped usage: params placed sharded over pipe axis, classifier
    # head on top, one SGD step decreases the loss.
    from jax.sharding import NamedSharding
    mesh = MeshTopology(pipeline=4).build()
    stack = PipelineStack(_block, depth=4)
    crit = nn.MSECriterion()
    specs = pipeline_spec_tree(stack)
    params = jax.tree_util.tree_map(
        lambda leaf, sp: jax.device_put(leaf, NamedSharding(mesh, sp)),
        stack.parameter_tree(), specs)
    x, y = _rand(8, 5, 16), _rand(8, 5, 16)

    loss_fn = gpipe_loss_fn(stack, crit, mesh, n_micro=4)
    vg = jax.jit(jax.value_and_grad(lambda p: loss_fn(p, None, x, y)))
    l0, g = vg(params)
    params2 = jax.tree_util.tree_map(lambda p, gg: p - 0.05 * gg, params, g)
    l1, _ = vg(params2)
    assert float(l1) < float(l0)


def test_compile_time_flat_in_n_micro():
    # The schedule loop is a lax.scan: the traced program must not grow
    # with the microbatch count (the round-2 Python-unrolled loop did).
    mesh = MeshTopology(pipeline=4).build()
    stack = PipelineStack(_block, depth=4)
    crit = nn.MSECriterion()
    params = stack.parameter_tree()

    def n_eqns(n_micro, batch):
        x, y = _rand(batch, 5, 16), _rand(batch, 5, 16)
        loss_fn = gpipe_loss_fn(stack, crit, mesh, n_micro=n_micro)
        jaxpr = jax.make_jaxpr(lambda p: loss_fn(p, None, x, y))(params)
        return sum(1 for _ in jaxpr.jaxpr.eqns)

    assert n_eqns(4, 16) == n_eqns(32, 32 * 4)


def test_gpipe_many_microbatches():
    # n_micro = 4x stages (the bubble-amortised regime): parity holds.
    mesh = MeshTopology(pipeline=4).build()
    stack = PipelineStack(_block, depth=4)
    crit = nn.MSECriterion()
    x, y = _rand(16, 4, 16), _rand(16, 4, 16)
    loss_fn = gpipe_loss_fn(stack, crit, mesh, n_micro=16)
    loss_pp = jax.jit(loss_fn)(stack.parameter_tree(), None, x, y)
    loss_seq = crit.apply(stack.forward(x), y)
    np.testing.assert_allclose(float(loss_pp), float(loss_seq),
                               rtol=1e-5, atol=1e-5)


class TestCircularSchedule:
    def _run(self, depth, p, v, n_micro, grads=False):
        from bigdl_tpu.parallel.pipeline import (circular_permutation,
                                                 schedule_length)
        mesh = MeshTopology(pipeline=p).build()
        stack = PipelineStack(_block, depth=depth)
        crit = nn.MSECriterion()
        x, y = _rand(n_micro, 4, 16), _rand(n_micro, 4, 16)
        params = stack.parameter_tree()
        perm = jnp.asarray(circular_permutation(depth, p, v))
        permuted = jax.tree_util.tree_map(lambda leaf: leaf[perm], params)
        loss_fn = gpipe_loss_fn(stack, crit, mesh, n_micro=n_micro,
                                interleave=v)
        # bubble: V-fold shorter than V sequential GPipe rides
        assert schedule_length(n_micro, p, v) == n_micro * v + p - 1

        loss_pp = jax.jit(loss_fn)(permuted, None, x, y)
        loss_seq = crit.apply(stack.forward(x), y)
        np.testing.assert_allclose(float(loss_pp), float(loss_seq),
                                   rtol=1e-5, atol=1e-5)
        if grads:
            g_pp = jax.jit(jax.grad(
                lambda pp: loss_fn(pp, None, x, y)))(permuted)
            # un-permute the pipeline grads back to true layer order
            inv = jnp.asarray(np.argsort(np.asarray(perm)))
            g_pp = jax.tree_util.tree_map(lambda leaf: leaf[inv], g_pp)
            g_seq = jax.grad(lambda pp: crit.apply(
                stack.scan_apply(pp, x), y))(params)
            for a, b in zip(jax.tree_util.tree_leaves(g_pp),
                            jax.tree_util.tree_leaves(g_seq)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-4, atol=1e-4)

    def test_interleave2_matches_sequential(self):
        self._run(depth=8, p=4, v=2, n_micro=8)

    def test_interleave2_min_microbatches(self):
        self._run(depth=8, p=4, v=2, n_micro=4)  # M == P edge (delay 0)

    def test_interleave2_grads(self):
        self._run(depth=8, p=4, v=2, n_micro=8, grads=True)

    def test_multi_layer_chunks(self):
        self._run(depth=16, p=4, v=2, n_micro=6)


class TestBufferedStack:
    def _bn_block(self):
        # conv + BatchNorm + ReLU residual-ish block, shape-preserving
        return (nn.Sequential()
                .add(nn.SpatialConvolution(8, 8, 3, 3, 1, 1, 1, 1,
                                           with_bias=False))
                .add(nn.SpatialBatchNormalization(8))
                .add(nn.ReLU()))

    def test_stack_carries_buffers(self):
        stack = PipelineStack(self._bn_block, depth=4)
        assert stack.has_buffers
        x = _rand(4, 6, 6, 8)
        stack.training_mode()
        before = jax.tree_util.tree_leaves(stack.buffer_tree())[0].copy()
        stack.forward(x)
        after = jax.tree_util.tree_leaves(stack.buffer_tree())[0]
        assert not np.allclose(np.asarray(before), np.asarray(after))

    def test_gpipe_buffered_matches_microbatch_sequential(self):
        # Oracle: the same stack run microbatch-by-microbatch sequentially
        # (BN stats update per microbatch — gradient-accumulation semantics)
        mesh = MeshTopology(pipeline=4).build()
        stack = PipelineStack(self._bn_block, depth=4)
        crit = nn.MSECriterion()
        n_micro = 4
        x, y = _rand(8, 6, 6, 8), _rand(8, 6, 6, 8)
        params, bufs = stack.parameter_tree(), stack.buffer_tree()

        loss_fn = gpipe_loss_fn(stack, crit, mesh, n_micro=n_micro)
        loss_pp, new_bufs = jax.jit(loss_fn)(params, bufs, None, x, y)

        mbs = x.reshape(n_micro, 2, 6, 6, 8)
        ybs = y.reshape(n_micro, 2, 6, 6, 8)
        b_seq = bufs
        total = 0.0
        for i in range(n_micro):
            out, b_seq = stack.scan_apply(params, mbs[i], training=True,
                                          buffers=b_seq)
            total += float(crit.apply(out, ybs[i]))
        np.testing.assert_allclose(float(loss_pp), total / n_micro,
                                   rtol=1e-4, atol=1e-4)
        for a, b in zip(jax.tree_util.tree_leaves(new_bufs),
                        jax.tree_util.tree_leaves(b_seq)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)


def test_dp_x_pp_matches_sequential():
    # data=2 x pipe=4: each data group pipelines its batch slice; pmean'd
    # loss and grads match the full-batch sequential oracle
    mesh = MeshTopology(data=2, pipeline=4).build()
    stack = PipelineStack(_block, depth=4)
    crit = nn.MSECriterion()
    x, y = _rand(8, 4, 16), _rand(8, 4, 16)
    params = stack.parameter_tree()
    loss_fn = gpipe_loss_fn(stack, crit, mesh, n_micro=4,
                            data_axis="data")
    loss_pp = jax.jit(loss_fn)(params, None, x, y)
    loss_seq = crit.apply(stack.forward(x), y)
    np.testing.assert_allclose(float(loss_pp), float(loss_seq),
                               rtol=1e-5, atol=1e-5)
    g_pp = jax.jit(jax.grad(lambda p: loss_fn(p, None, x, y)))(params)
    g_seq = jax.grad(lambda p: crit.apply(stack.scan_apply(p, x), y))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_pp),
                    jax.tree_util.tree_leaves(g_seq)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
