"""Gradient clipping: L2-norm and constant, across every step builder."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.dataset.base import DataSet, Sample, SampleToBatch
from bigdl_tpu.optim import SGD, Optimizer, Trigger
from bigdl_tpu.optim.optimizer import make_grad_clipper


def tree_norm(tree):
    return float(jnp.sqrt(sum(jnp.sum(jnp.square(x))
                              for x in jax.tree_util.tree_leaves(tree))))


class TestClipper:
    def test_l2_scales_only_when_over(self):
        clip = make_grad_clipper({"l2": 1.0})
        g = {"a": jnp.asarray([3.0, 4.0])}  # norm 5 -> scaled to 1
        out = clip(g)
        np.testing.assert_allclose(tree_norm(out), 1.0, rtol=1e-5)
        small = {"a": jnp.asarray([0.3, 0.4])}  # norm .5 -> untouched
        np.testing.assert_allclose(np.asarray(clip(small)["a"]),
                                   np.asarray(small["a"]), rtol=1e-6)

    def test_constant_clamps(self):
        clip = make_grad_clipper({"constant": (-0.1, 0.1)})
        out = clip({"a": jnp.asarray([-5.0, 0.05, 5.0])})
        np.testing.assert_allclose(np.asarray(out["a"]), [-0.1, 0.05, 0.1])

    def test_identity(self):
        clip = make_grad_clipper({})
        g = {"a": jnp.asarray([7.0])}
        assert clip(g) is g

    def test_l2_preserves_dtype(self):
        clip = make_grad_clipper({"l2": 0.5})
        out = clip({"a": jnp.asarray([10.0], jnp.bfloat16)})
        assert out["a"].dtype == jnp.bfloat16


def make_data(n=16, dim=8):
    rng = np.random.RandomState(0)
    return [Sample(rng.randn(dim).astype(np.float32) * 50.0,  # big inputs
                   np.float32(rng.randint(1, 3)))
            for _ in range(n)]


def build_model(dim=8):
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(7)
    return (nn.Sequential().add(nn.Linear(dim, 16)).add(nn.ReLU())
            .add(nn.Linear(16, 2)).add(nn.LogSoftMax()))


def run_steps(distributed=False, clip=None, iters=2):
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(123)
    model = build_model()
    ds = DataSet.array(make_data(), distributed=distributed).transform(
        SampleToBatch(batch_size=8))
    if distributed:
        from bigdl_tpu.parallel import MeshTopology
        from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
        opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(),
                              topology=MeshTopology.data_parallel())
    else:
        opt = Optimizer(model, ds, nn.ClassNLLCriterion())
    opt.set_optim_method(SGD(learningrate=1.0))  # big LR amplifies grads
    opt.set_end_when(Trigger.max_iteration(iters))
    if clip == "l2":
        opt.set_gradient_clipping_by_l2_norm(0.01)
    elif clip == "constant":
        opt.set_constant_gradient_clipping(-1e-4, 1e-4)
    before, _ = model.get_parameters()
    trained = opt.optimize()
    after, _ = trained.get_parameters()
    return float(jnp.linalg.norm(after - before))


class TestOptimizerClipping:
    def test_l2_bounds_update_local(self):
        # SGD lr=1: per-step ||delta|| == ||clipped grad|| <= 0.01
        moved = run_steps(clip="l2", iters=2)
        assert moved <= 2 * 0.01 + 1e-6
        unclipped = run_steps(clip=None, iters=2)
        assert unclipped > moved * 5  # clipping actually bit

    def test_constant_bounds_update_local(self):
        moved = run_steps(clip="constant", iters=1)
        # every element moved at most 1e-4 (lr 1)
        assert moved <= 1e-4 * np.sqrt(8 * 16 + 16 + 16 * 2 + 2) + 1e-6

    def test_l2_bounds_update_distributed(self):
        moved = run_steps(distributed=True, clip="l2", iters=2)
        assert moved <= 2 * 0.01 + 1e-6

    def test_l2_bounds_update_sharded(self):
        from bigdl_tpu.utils.rng import manual_seed
        from bigdl_tpu.parallel import MeshTopology
        from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
        manual_seed(123)
        model = build_model()
        ds = DataSet.array(make_data(), distributed=True).transform(
            SampleToBatch(batch_size=8))
        opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(),
                              topology=MeshTopology.data_parallel())
        opt.sync_mode = "sharded"
        opt.set_optim_method(SGD(learningrate=1.0))
        opt.set_end_when(Trigger.max_iteration(2))
        opt.set_gradient_clipping_by_l2_norm(0.01)
        before, _ = model.get_parameters()
        trained = opt.optimize()
        after, _ = trained.get_parameters()
        assert float(jnp.linalg.norm(after - before)) <= 2 * 0.01 + 1e-6

    def test_setter_validation(self):
        model = build_model()
        ds = DataSet.array(make_data()).transform(SampleToBatch(batch_size=8))
        opt = Optimizer(model, ds, nn.ClassNLLCriterion())
        with pytest.raises(ValueError):
            opt.set_gradient_clipping_by_l2_norm(0.0)
        with pytest.raises(ValueError):
            opt.set_constant_gradient_clipping(1.0, -1.0)
        opt.set_gradient_clipping_by_l2_norm(5.0)
        opt.disable_gradient_clipping()
        assert opt._grad_clip == {}

    def test_both_modes_compose(self):
        # constant clamp first, then the global-norm bound on the result
        clip = make_grad_clipper({"constant": (-0.1, 0.1), "l2": 0.05})
        out = clip({"a": jnp.asarray([5.0, -5.0, 0.01])})
        arr = np.asarray(out["a"])
        assert np.abs(arr).max() <= 0.1 + 1e-7          # clamp applied
        assert np.linalg.norm(arr) <= 0.05 + 1e-6       # then norm bound

    def test_both_setters_stack(self):
        model = build_model()
        ds = DataSet.array(make_data()).transform(SampleToBatch(batch_size=8))
        opt = Optimizer(model, ds, nn.ClassNLLCriterion())
        opt.set_gradient_clipping_by_l2_norm(1.0)
        opt.set_constant_gradient_clipping(-0.1, 0.1)
        assert opt._grad_clip == {"l2": 1.0, "constant": (-0.1, 0.1)}


class TestAdamW:
    def test_matches_torch_adamw(self):
        import torch
        from bigdl_tpu.optim import AdamW

        rng = np.random.RandomState(0)
        w0 = rng.randn(6, 4).astype(np.float32)
        grads_seq = [rng.randn(6, 4).astype(np.float32) for _ in range(5)]

        # torch oracle
        tw = torch.nn.Parameter(torch.from_numpy(w0.copy()))
        topt = torch.optim.AdamW([tw], lr=1e-2, betas=(0.9, 0.999),
                                 eps=1e-8, weight_decay=0.1)
        for g in grads_seq:
            tw.grad = torch.from_numpy(g.copy())
            topt.step()

        method = AdamW(learningrate=1e-2, weightdecay=0.1)
        params = {"w": jnp.asarray(w0)}
        state = method.init_state(params)
        for g in grads_seq:
            params, state = method.update({"w": jnp.asarray(g)}, state,
                                          params)
        np.testing.assert_allclose(np.asarray(params["w"]),
                                   tw.detach().numpy(), atol=1e-6)

    def test_decay_actually_decoupled(self):
        from bigdl_tpu.optim import Adam, AdamW
        w = {"w": jnp.full((3,), 10.0)}
        # zero gradient: AdamW shrinks weights by exactly (1 - lr*decay)
        aw = AdamW(learningrate=0.1, weightdecay=0.5)
        out, _ = aw.update({"w": jnp.zeros(3)}, aw.init_state(w), w)
        np.testing.assert_allclose(np.asarray(out["w"]), 10.0 * (1 - 0.05),
                                   rtol=1e-6)
        # coupled-L2 Adam instead routes decay through the moments: the
        # first zero-grad step moves by ~lr/(1+eps'), NOT by lr*decay*w
        ad = Adam(learningrate=0.1, weightdecay=0.5)
        out2, _ = ad.update({"w": jnp.zeros(3)}, ad.init_state(w), w)
        assert not np.allclose(np.asarray(out2["w"]), 10.0 * (1 - 0.05),
                               rtol=1e-3)

    def test_adamw_reports_decay(self):
        from bigdl_tpu.optim import AdamW
        hp = AdamW(weightdecay=0.1).get_hyper_parameter()
        assert float(hp["weightDecay"]) == 0.1

    def test_warmup_cosine_continuous(self):
        from bigdl_tpu.optim import CosineDecay, Warmup
        sched = Warmup(10, CosineDecay(100))
        # last warmup step reaches base_lr; first post-warmup step is the
        # cosine's START (no discontinuous drop)
        r_last = float(sched.rate(1.0, {"evalCounter": jnp.asarray(9)}))
        r_next = float(sched.rate(1.0, {"evalCounter": jnp.asarray(10)}))
        np.testing.assert_allclose(r_last, 1.0, rtol=1e-6)
        np.testing.assert_allclose(r_next, 1.0, rtol=1e-6)
        r_end = float(sched.rate(1.0, {"evalCounter": jnp.asarray(110)}))
        np.testing.assert_allclose(r_end, 0.0, atol=1e-7)


class TestShardedPadLanes:
    def test_asymmetric_clamp_parity_with_allreduce(self):
        """178 params over 8 devices leaves 6 pad lanes; a clamp range
        excluding 0 must NOT lift them into the global norm (regression:
        sharded and allreduce modes diverged)."""
        from bigdl_tpu.utils.rng import manual_seed
        from bigdl_tpu.parallel import MeshTopology
        from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer

        def run(sync_mode):
            manual_seed(123)
            model = build_model()
            ds = DataSet.array(make_data(), distributed=True).transform(
                SampleToBatch(batch_size=8))
            opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(),
                                  topology=MeshTopology.data_parallel())
            opt.sync_mode = sync_mode
            opt.set_optim_method(SGD(learningrate=0.1))
            opt.set_end_when(Trigger.max_iteration(2))
            opt.set_constant_gradient_clipping(0.05, 1.0)  # excludes 0
            opt.set_gradient_clipping_by_l2_norm(0.5)
            trained = opt.optimize()
            flat, _ = trained.get_parameters()
            return np.asarray(flat)

        np.testing.assert_allclose(run("sharded"), run("allreduce"),
                                   atol=2e-6)


class TestCosineDecay:
    def test_endpoints_and_midpoint(self):
        from bigdl_tpu.optim import CosineDecay
        sched = CosineDecay(100, min_lr=0.1)
        r0 = float(sched.rate(1.0, {"evalCounter": jnp.asarray(0)}))
        rm = float(sched.rate(1.0, {"evalCounter": jnp.asarray(50)}))
        re_ = float(sched.rate(1.0, {"evalCounter": jnp.asarray(100)}))
        rpast = float(sched.rate(1.0, {"evalCounter": jnp.asarray(500)}))
        np.testing.assert_allclose(r0, 1.0, rtol=1e-6)
        np.testing.assert_allclose(rm, 0.55, rtol=1e-6)  # (1+0.1)/2
        np.testing.assert_allclose(re_, 0.1, rtol=1e-6)
        np.testing.assert_allclose(rpast, 0.1, rtol=1e-6)  # clamps

    def test_warmup_cosine_composition_trains(self):
        from bigdl_tpu.optim import CosineDecay, Warmup
        sched = Warmup(2, CosineDecay(10))
        from bigdl_tpu.utils.rng import manual_seed
        manual_seed(3)
        model = build_model()
        ds = DataSet.array(make_data()).transform(SampleToBatch(batch_size=8))
        opt = Optimizer(model, ds, nn.ClassNLLCriterion())
        opt.set_optim_method(SGD(learningrate=0.1,
                                 learningrate_schedule=sched))
        opt.set_end_when(Trigger.max_iteration(4))
        opt.optimize()
