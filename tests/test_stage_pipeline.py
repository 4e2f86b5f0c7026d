"""Heterogeneous stage-list pipelining (``parallel.StagePipeline``) — the
round-4 closure of "PipelineStack requires homogeneous blocks": a REAL
model (embedding + blocks + vocab head; downsampling conv stages) pipelines
end-to-end, verified DIFFERENTIALLY against the sequential forward (the
repo's RefOptimizer tradition, ``$T/optim/RefDistriOptimizerSpec`` style:
the schedule must reproduce the unpipelined math exactly)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.parallel.mesh import MeshTopology
from bigdl_tpu.parallel.pipeline import (StagePipeline,
                                         stage_pipeline_loss_fn)


def _lm_stages(vocab=24, e=16, heads=2, ffn=32, seed=5):
    """3 heterogeneous stages: tokens->hidden, hidden->hidden,
    hidden->log-probs — the embed+blocks+head shape PipelineStack cannot
    express."""
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(seed)
    s0 = nn.Sequential().add(nn.LookupTable(vocab, e)) \
        .add(nn.PositionalEncoding(e, 32)) \
        .add(nn.TransformerEncoderLayer(e, heads, ffn, causal=True))
    s1 = nn.Sequential().add(nn.TransformerEncoderLayer(e, heads, ffn,
                                                        causal=True))
    s2 = nn.Sequential().add(nn.LayerNorm(e)) \
        .add(nn.TimeDistributed(nn.Linear(e, vocab))).add(nn.LogSoftMax())
    return [s0, s1, s2]


def _conv_stages(seed=9):
    """Downsampling conv stages: every boundary has a DIFFERENT shape
    ((8,8,4) -> (4,4,8) -> flat 10) — the ResNet-stage pattern."""
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(seed)
    s0 = nn.Sequential().add(nn.SpatialConvolution(1, 4, 3, 3, 2, 2, 1, 1)) \
        .add(nn.ReLU())
    s1 = nn.Sequential().add(nn.SpatialConvolution(4, 8, 3, 3, 2, 2, 1, 1)) \
        .add(nn.ReLU())
    s2 = nn.Sequential().add(nn.Reshape((2 * 2 * 8,))) \
        .add(nn.Linear(2 * 2 * 8, 10)).add(nn.LogSoftMax())
    return [s0, s1, s2]


class TestStagePipelineLM:
    def _setup(self):
        stages = _lm_stages()
        rng = np.random.default_rng(0)
        x = rng.integers(1, 25, (8, 8)).astype(np.float32)
        y = rng.integers(1, 25, (8, 8)).astype(np.float32)
        pipe = StagePipeline(stages, sample_microbatch=x[:2])
        crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion())
        mesh = MeshTopology(pipeline=3,
                            devices=jax.devices()[:3]).build()
        return pipe, crit, mesh, jnp.asarray(x), jnp.asarray(y)

    def test_loss_matches_sequential(self):
        pipe, crit, mesh, x, y = self._setup()
        loss_fn = stage_pipeline_loss_fn(pipe, crit, mesh, n_micro=4)
        got = jax.jit(loss_fn)(pipe.parameter_tree(), x, y)
        ref_out = pipe.sequential_apply(pipe.parameter_tree(), x)
        ref = crit.apply(ref_out, y)
        np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)

    def test_grads_match_sequential(self):
        pipe, crit, mesh, x, y = self._setup()
        loss_fn = stage_pipeline_loss_fn(pipe, crit, mesh, n_micro=4)

        def seq_loss(p):
            return crit.apply(pipe.sequential_apply(p, x), y) \
                .astype(jnp.float32)

        g_pipe = jax.jit(jax.grad(lambda p: loss_fn(p, x, y)))(
            pipe.parameter_tree())
        g_ref = jax.grad(seq_loss)(pipe.parameter_tree())
        np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_ref),
                                   rtol=2e-4, atol=1e-6)

    def test_remat_grads_exact(self):
        pipe, crit, mesh, x, y = self._setup()
        f0 = stage_pipeline_loss_fn(pipe, crit, mesh, n_micro=4)
        f1 = stage_pipeline_loss_fn(pipe, crit, mesh, n_micro=4, remat=True)
        g0 = jax.jit(jax.grad(lambda p: f0(p, x, y)))(pipe.parameter_tree())
        g1 = jax.jit(jax.grad(lambda p: f1(p, x, y)))(pipe.parameter_tree())
        # remat replays the forward with different fusion groupings, so
        # agreement is float-level, not bitwise
        np.testing.assert_allclose(np.asarray(g0), np.asarray(g1),
                                   rtol=1e-5, atol=1e-6)

    def test_unstack_roundtrip(self):
        pipe, *_ = self._setup()
        trees = pipe.unstack_parameter_trees(pipe.parameter_tree())
        assert len(trees) == 3
        for st, tree in zip(pipe.stages, trees):
            ref = st.parameter_tree()
            jax.tree_util.tree_map(
                lambda a, b: np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b)), tree, ref)


class TestStagePipelineConv:
    def test_heterogeneous_shapes_loss_and_grads(self):
        stages = _conv_stages()
        rng = np.random.default_rng(1)
        x = rng.normal(0, 1, (8, 8, 8, 1)).astype(np.float32)
        y = rng.integers(1, 11, (8,)).astype(np.float32)
        pipe = StagePipeline(stages, sample_microbatch=x[:2])
        # every boundary a different size; conduit = the largest of the
        # stage inputs ((8,8,1) -> (4,4,4) -> (2,2,8)) and the (10,) output
        assert pipe.conduit_len == max(2 * 8 * 8 * 1, 2 * 4 * 4 * 4,
                                       2 * 2 * 2 * 8, 2 * 10)
        crit = nn.ClassNLLCriterion()
        mesh = MeshTopology(pipeline=3, devices=jax.devices()[:3]).build()
        loss_fn = stage_pipeline_loss_fn(pipe, crit, mesh, n_micro=4)
        xj, yj = jnp.asarray(x), jnp.asarray(y)
        got = jax.jit(loss_fn)(pipe.parameter_tree(), xj, yj)
        ref = crit.apply(pipe.sequential_apply(pipe.parameter_tree(), xj),
                         yj)
        np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
        g = jax.jit(jax.grad(lambda p: loss_fn(p, xj, yj)))(
            pipe.parameter_tree())
        g_ref = jax.grad(lambda p: crit.apply(
            pipe.sequential_apply(p, xj), yj).astype(jnp.float32))(
            pipe.parameter_tree())
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=2e-4, atol=1e-6)


class TestStagePipelineDpPp:
    def test_dp_x_pp_composition(self):
        stages = _lm_stages(seed=7)
        rng = np.random.default_rng(2)
        x = rng.integers(1, 25, (16, 8)).astype(np.float32)
        y = rng.integers(1, 25, (16, 8)).astype(np.float32)
        pipe = StagePipeline(stages, sample_microbatch=x[:2])
        crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion())
        mesh = MeshTopology(data=2, pipeline=3,
                            devices=jax.devices()[:6]).build()
        loss_fn = stage_pipeline_loss_fn(pipe, crit, mesh, n_micro=4,
                                         data_axis="data")
        xj, yj = jnp.asarray(x), jnp.asarray(y)
        got = jax.jit(loss_fn)(pipe.parameter_tree(), xj, yj)
        ref = crit.apply(pipe.sequential_apply(pipe.parameter_tree(), xj),
                         yj)
        # dp groups see disjoint batch halves; pmean of per-group means ==
        # global mean only when the criterion means per element (it does)
        np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)


class TestStagePipelineValidation:
    def test_rejects_buffered_stages(self):
        s0 = nn.Sequential().add(nn.SpatialConvolution(1, 4, 3, 3)) \
            .add(nn.SpatialBatchNormalization(4))
        s1 = nn.Sequential().add(nn.Linear(4, 2))
        with pytest.raises(ValueError, match="buffer"):
            StagePipeline([s0, s1], sample_microbatch=np.zeros((1, 8, 8, 1)))

    def test_rejects_single_stage(self):
        with pytest.raises(ValueError, match="2 stages"):
            StagePipeline([nn.Sequential().add(nn.Linear(4, 4))],
                          sample_microbatch=np.zeros((1, 4)))

    def test_mesh_stage_mismatch_raises(self):
        stages = _lm_stages()
        x = np.ones((4, 8), np.float32)
        pipe = StagePipeline(stages, sample_microbatch=x[:2])
        crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion())
        mesh = MeshTopology(pipeline=4, devices=jax.devices()[:4]).build()
        loss_fn = stage_pipeline_loss_fn(pipe, crit, mesh, n_micro=2)
        with pytest.raises(AssertionError, match="stage count"):
            jax.jit(loss_fn)(
                np.zeros((4, pipe.max_param_len), np.float32),
                jnp.asarray(x), jnp.asarray(x))
