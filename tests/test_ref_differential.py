"""Differential reference-optimizer tests (reference strategy §4.3:
``$T/optim/RefDistriOptimizer.scala:31`` / ``RefLocalOptimizer.scala`` —
a naive, obviously-correct serial trainer; the production optimizer must
converge to the same weights)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu as bt
from bigdl_tpu import nn
from bigdl_tpu.dataset.base import DataSet, MiniBatch, Sample, SampleToBatch
from bigdl_tpu.nn.module import functional_apply
from bigdl_tpu.optim import Optimizer, SGD, Trigger


def _fixed_batches(n_batches=4, batch=16, dim=6, classes=3, seed=0):
    rng = np.random.RandomState(seed)
    batches = []
    for _ in range(n_batches):
        x = rng.randn(batch, dim).astype(np.float32)
        y = rng.randint(1, classes + 1, batch).astype(np.float32)
        batches.append((x, y))
    return batches


class _FixedDataSet(DataSet if False else object):
    """Deterministic dataset: serves exactly the given batches per epoch."""

    def __init__(self, batches):
        self.batches = batches

    def data(self, train):
        for x, y in self.batches:
            yield MiniBatch(x, y)

    def size(self):
        return sum(b[0].shape[0] for b in self.batches)

    def shuffle(self):
        pass  # deterministic by construction

    def is_distributed(self):
        return False


class RefOptimizer:
    """The naive trainer: plain gradient descent with momentum, one batch at
    a time, no jit, float64-free — mirrors RefLocalOptimizer's role as the
    obviously-correct oracle."""

    def __init__(self, model, criterion, lr, momentum=0.0):
        self.model = model
        self.criterion = criterion
        self.lr = lr
        self.momentum = momentum

    def train(self, batches, epochs):
        params = self.model.parameter_tree()
        buffers = self.model.buffer_tree()
        velocity = jax.tree_util.tree_map(jnp.zeros_like, params)

        def loss_fn(p, x, y):
            out, _ = functional_apply(self.model, p, buffers, x, training=True)
            return self.criterion.apply(out, y)

        grad_fn = jax.grad(loss_fn)
        for _ in range(epochs):
            for x, y in batches:
                g = grad_fn(params, jnp.asarray(x), jnp.asarray(y))
                if self.momentum:
                    # Torch sgd convention: dampening defaults to momentum,
                    # v = m*v + (1-m)*g (reference optim/SGD.scala)
                    m = self.momentum
                    velocity = jax.tree_util.tree_map(
                        lambda v, gr: m * v + (1 - m) * gr, velocity, g)
                    use = velocity
                else:
                    use = g
                params = jax.tree_util.tree_map(
                    lambda p, u: p - self.lr * u, params, use)
        return params


def _flat(params):
    return np.concatenate([np.asarray(l).ravel()
                           for l in jax.tree_util.tree_leaves(params)])


class TestDifferential:
    @pytest.mark.parametrize("momentum", [0.0, 0.9])
    def test_local_optimizer_matches_reference(self, momentum):
        batches = _fixed_batches()
        bt.utils.manual_seed(7)
        model_a = nn.Sequential().add(nn.Linear(6, 3)).add(nn.LogSoftMax())
        init = model_a.parameter_tree()

        ref_params = RefOptimizer(model_a, nn.ClassNLLCriterion(),
                                  lr=0.1, momentum=momentum).train(batches, 2)

        # production path on an identical twin
        model_b = nn.Sequential().add(nn.Linear(6, 3)).add(nn.LogSoftMax())
        model_b.load_parameter_tree(init)
        opt = Optimizer(model_b, _FixedDataSet(batches),
                        nn.ClassNLLCriterion())
        opt.set_optim_method(SGD(learningrate=0.1, momentum=momentum))
        opt.set_end_when(Trigger.max_epoch(2))
        trained = opt.optimize()

        got = _flat(trained.parameter_tree())
        want = _flat(ref_params)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_distri_matches_local_on_mesh(self):
        """DP over the 8-device mesh must equal the single-replica result
        (the reference's DistriOptimizerSpec vs RefDistriOptimizer check)."""
        from bigdl_tpu.parallel import MeshTopology
        from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer

        batches = _fixed_batches(n_batches=2, batch=32)
        bt.utils.manual_seed(9)
        model_a = nn.Sequential().add(nn.Linear(6, 3)).add(nn.LogSoftMax())
        init = model_a.parameter_tree()
        ref = RefOptimizer(model_a, nn.ClassNLLCriterion(), lr=0.05)\
            .train(batches, 1)

        model_b = nn.Sequential().add(nn.Linear(6, 3)).add(nn.LogSoftMax())
        model_b.load_parameter_tree(init)
        opt = DistriOptimizer(model_b, _FixedDataSet(batches),
                              nn.ClassNLLCriterion(),
                              topology=MeshTopology.data_parallel())
        opt.set_optim_method(SGD(learningrate=0.05))
        opt.set_end_when(Trigger.max_epoch(1))
        trained = opt.optimize()
        np.testing.assert_allclose(_flat(trained.parameter_tree()),
                                   _flat(ref), rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# ZeRO-1 sharded plane differential matrix (round-2: VERDICT #6).
# The reference cross-checks BOTH optimizers against a naive implementation
# across configs ($T/optim/RefDistriOptimizer.scala:31 + RefLocalOptimizer);
# here the ZeRO-1 slice-ownership path must match the allreduce path for
# every OptimMethod, and both must match independent numpy oracles.
# ---------------------------------------------------------------------------

from bigdl_tpu.optim import Adam, Adagrad, Adamax, Adadelta, RMSprop
from bigdl_tpu.optim.methods import Poly, Step


def _train_distri(batches, init, mk_method, sync_mode, epochs=2):
    from bigdl_tpu.parallel import MeshTopology
    from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer

    model = nn.Sequential().add(nn.Linear(6, 8)).add(nn.Tanh())
    model.add(nn.Linear(8, 3)).add(nn.LogSoftMax())
    model.load_parameter_tree(init)
    opt = DistriOptimizer(model, _FixedDataSet(batches),
                          nn.ClassNLLCriterion(),
                          topology=MeshTopology.data_parallel(),
                          sync_mode=sync_mode)
    opt.set_optim_method(mk_method())
    opt.set_end_when(Trigger.max_epoch(epochs))
    return _flat(opt.optimize().parameter_tree())


def _fresh_init(seed=11):
    bt.utils.manual_seed(seed)
    m = nn.Sequential().add(nn.Linear(6, 8)).add(nn.Tanh())
    m.add(nn.Linear(8, 3)).add(nn.LogSoftMax())
    return m.parameter_tree()


SHARDED_METHODS = [
    ("sgd", lambda: SGD(learningrate=0.1)),
    ("sgd-mom", lambda: SGD(learningrate=0.1, momentum=0.9)),
    ("sgd-mom-wd", lambda: SGD(learningrate=0.1, momentum=0.9,
                               weightdecay=1e-3)),
    ("sgd-nesterov", lambda: SGD(learningrate=0.1, momentum=0.9,
                                 dampening=0.0, nesterov=True)),
    ("sgd-poly", lambda: SGD(learningrate=0.1,
                             learningrate_schedule=Poly(0.5, 100))),
    ("sgd-step", lambda: SGD(learningrate=0.1,
                             learningrate_schedule=Step(3, 0.5))),
    ("adam", lambda: Adam(learningrate=0.01)),
    ("rmsprop", lambda: RMSprop(learningrate=0.01)),
    ("adagrad", lambda: Adagrad(learningrate=0.05)),
    ("adamax", lambda: Adamax()),
    ("adadelta", lambda: Adadelta()),
]


class TestShardedDifferential:
    """sync_mode='sharded' (ZeRO-1 slice ownership: psum_scatter + slice
    update + all_gather) must be numerically interchangeable with
    sync_mode='allreduce' (replicated update after psum) for every
    OptimMethod: elementwise updates commute with flat slicing."""

    @pytest.mark.parametrize("name,mk", SHARDED_METHODS,
                             ids=[m[0] for m in SHARDED_METHODS])
    def test_sharded_matches_allreduce(self, name, mk):
        batches = _fixed_batches(n_batches=3, batch=32)
        init = _fresh_init()
        a = _train_distri(batches, init, mk, "allreduce")
        s = _train_distri(batches, init, mk, "sharded")
        np.testing.assert_allclose(s, a, rtol=1e-5, atol=1e-6)


def _np_oracle_train(batches, init, update_fn, epochs=2):
    """Naive numpy trainer: independent of OptimMethod.update — jax only
    supplies gradients (autodiff is the common substrate, the optimizer
    math is reimplemented in numpy)."""
    model = nn.Sequential().add(nn.Linear(6, 8)).add(nn.Tanh())
    model.add(nn.Linear(8, 3)).add(nn.LogSoftMax())
    model.load_parameter_tree(init)
    crit = nn.ClassNLLCriterion()
    params = model.parameter_tree()
    buffers = model.buffer_tree()

    def loss_fn(p, x, y):
        out, _ = functional_apply(model, p, buffers, x, training=True)
        return crit.apply(out, y)

    grad_fn = jax.grad(loss_fn)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    w = [np.asarray(l, np.float32) for l in leaves]
    slot = [None] * len(w)
    t = 0
    for _ in range(epochs):
        for x, y in batches:
            g_tree = grad_fn(jax.tree_util.tree_unflatten(treedef, w),
                             jnp.asarray(x), jnp.asarray(y))
            g = [np.asarray(l, np.float32)
                 for l in jax.tree_util.tree_leaves(g_tree)]
            t += 1
            for i in range(len(w)):
                w[i], slot[i] = update_fn(w[i], g[i], slot[i], t)
    return np.concatenate([x.ravel() for x in w])


def _np_adam_update(lr=0.01, b1=0.9, b2=0.999, eps=1e-8):
    def f(w, g, slot, t):
        m, v = slot if slot is not None else (np.zeros_like(w),
                                              np.zeros_like(w))
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        w = w - lr * (m / (1 - b1 ** t)) / (np.sqrt(v / (1 - b2 ** t)) + eps)
        return w, (m, v)
    return f


def _np_rmsprop_update(lr=0.01, rho=0.99, eps=1e-8):
    def f(w, g, slot, t):
        a = slot if slot is not None else np.zeros_like(w)
        a = rho * a + (1 - rho) * g * g
        return w - lr * g / (np.sqrt(a) + eps), a
    return f


class TestNumpyOracle:
    @pytest.mark.parametrize("sync_mode", ["allreduce", "sharded"])
    def test_adam_matches_numpy(self, sync_mode):
        batches = _fixed_batches(n_batches=3, batch=32)
        init = _fresh_init(13)
        want = _np_oracle_train(batches, init, _np_adam_update())
        got = _train_distri(batches, init, lambda: Adam(learningrate=0.01),
                            sync_mode)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("sync_mode", ["allreduce", "sharded"])
    def test_rmsprop_matches_numpy(self, sync_mode):
        batches = _fixed_batches(n_batches=3, batch=32)
        init = _fresh_init(17)
        want = _np_oracle_train(batches, init, _np_rmsprop_update())
        got = _train_distri(batches, init,
                            lambda: RMSprop(learningrate=0.01), sync_mode)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
