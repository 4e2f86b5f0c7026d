"""End-to-end training: LeNet on synthetic MNIST must converge, locally and
distributed over the 8-device virtual mesh, in both sync modes; distributed
must match single-chip results (the reference proves this with
``RefDistriOptimizer`` differential tests, ``$T/optim/DistriOptimizerSpec``).
"""

import logging

import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu as bt
from bigdl_tpu import nn
from bigdl_tpu.dataset import mnist
from bigdl_tpu.dataset.base import DataSet, SampleToBatch
from bigdl_tpu.dataset.image import BytesToGreyImg, GreyImgNormalizer, GreyImgToBatch
from bigdl_tpu.models import lenet
from bigdl_tpu.optim import (Loss, Optimizer, SGD, Top1Accuracy, Trigger)
from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
from bigdl_tpu.parallel.mesh import MeshTopology

logging.getLogger("bigdl_tpu.optim").setLevel(logging.WARNING)


def make_dataset(n=512, batch=64, distributed=False):
    records = mnist.synthetic(n)
    ds = DataSet.array(records, distributed=distributed)
    return ds >> BytesToGreyImg(28, 28) >> GreyImgNormalizer(33.0, 78.0) \
        >> GreyImgToBatch(batch)


def eval_accuracy(model, n=256):
    ds = make_dataset(n, 64)
    results = model.evaluate(ds, [Top1Accuracy()])
    return results[0][0].result()[0]


class TestLocalTraining:
    def test_lenet_converges(self):
        bt.utils.manual_seed(1)
        model = lenet.build(10)
        opt = Optimizer(model, make_dataset(), nn.ClassNLLCriterion())
        opt.set_optim_method(SGD(learningrate=0.1, momentum=0.9)) \
           .set_end_when(Trigger.max_epoch(4))
        trained = opt.optimize()
        acc = eval_accuracy(trained)
        assert acc > 0.9, f"LeNet failed to learn separable data: acc={acc}"

    def test_checkpoint_and_resume(self, tmp_path):
        bt.utils.manual_seed(2)
        model = lenet.build(10)
        ckpt = str(tmp_path / "ckpt")
        opt = Optimizer(model, make_dataset(128, 64), nn.ClassNLLCriterion())
        opt.set_optim_method(SGD(learningrate=0.05)) \
           .set_end_when(Trigger.max_epoch(1)) \
           .set_checkpoint(ckpt, Trigger.every_epoch())
        opt.optimize()
        import glob
        models = glob.glob(f"{ckpt}/model.*")
        # the resilience coordinator writes a state.N.resume.json marker
        # beside each snapshot — resume() wants the snapshot itself
        states = [s for s in glob.glob(f"{ckpt}/state.*")
                  if not s.endswith(".resume.json")]
        assert models and states
        # resume continues without error and advances epoch
        model2 = lenet.build(10)
        opt2 = Optimizer(model2, make_dataset(128, 64), nn.ClassNLLCriterion())
        opt2.set_optim_method(SGD(learningrate=0.05)) \
            .set_end_when(Trigger.max_epoch(2)) \
            .resume(models[0], states[0])
        opt2.optimize()

    def test_validation_hook(self):
        bt.utils.manual_seed(3)
        model = lenet.build(10)
        opt = Optimizer(model, make_dataset(128, 64), nn.ClassNLLCriterion())
        opt.set_optim_method(SGD(learningrate=0.05)) \
           .set_end_when(Trigger.max_epoch(1)) \
           .set_validation(Trigger.every_epoch(), make_dataset(128, 64),
                           [Top1Accuracy(), Loss(nn.ClassNLLCriterion())])
        trained = opt.optimize()
        assert trained is model

    def test_loss_sensitive_hook_sees_current_loss(self, tmp_path):
        # The pipelined loop publishes iteration i's loss one dispatch late;
        # a uses_loss hook trigger must force a drain so it observes THIS
        # iteration's loss (not i-1's, and never a missing first loss).
        from bigdl_tpu.visualization import TrainSummary
        bt.utils.manual_seed(4)
        seen = []

        class Probe:
            uses_loss = True

            def __call__(self, state):
                seen.append(float(state.get("trainingLoss", float("nan"))))
                return False

        model = lenet.build(10)
        summary = TrainSummary(str(tmp_path), "probe")
        opt = Optimizer(model, make_dataset(256, 64), nn.ClassNLLCriterion())
        opt.set_optim_method(SGD(learningrate=0.05)) \
           .set_end_when(Trigger.max_iteration(4)) \
           .set_train_summary(summary)
        opt.validation_trigger = Probe()
        opt.optimize()
        summary.close()
        logged = [v for _, v, _ in summary.read_scalar("Loss")]
        per_iter = seen[:len(logged)]
        assert logged and per_iter == pytest.approx(logged), (seen, logged)


class TestDistributedTraining:
    @pytest.mark.parametrize("sync_mode", ["allreduce", "sharded"])
    def test_lenet_distributed_converges(self, sync_mode):
        bt.utils.manual_seed(1)
        model = lenet.build(10)
        ds = make_dataset(512, 64, distributed=True)
        opt = Optimizer(model, ds, nn.ClassNLLCriterion())
        assert isinstance(opt, DistriOptimizer)
        opt.sync_mode = sync_mode
        opt.set_optim_method(SGD(learningrate=0.1, momentum=0.9)) \
           .set_end_when(Trigger.max_epoch(4))
        trained = opt.optimize()
        acc = eval_accuracy(trained)
        assert acc > 0.9, f"distributed ({sync_mode}) failed: acc={acc}"

    def test_distri_matches_local(self):
        """Differential test (reference ``RefDistriOptimizer`` pattern):
        same seed, same data order, one epoch — distributed allreduce must
        produce (near-)identical weights to the local loop."""
        def run(distributed):
            bt.utils.manual_seed(7)
            model = lenet.build(10)
            ds = make_dataset(256, 64, distributed=distributed)
            # fixed order: no shuffle difference — seed reset makes shuffles equal
            opt = Optimizer(model, ds, nn.ClassNLLCriterion())
            opt.set_optim_method(SGD(learningrate=0.05)) \
               .set_end_when(Trigger.max_epoch(1))
            return opt.optimize().get_parameters()[0]

        w_local = np.asarray(run(False))
        w_dist = np.asarray(run(True))
        np.testing.assert_allclose(w_local, w_dist, rtol=1e-3, atol=1e-5)

    def test_compressed_gradients(self):
        bt.utils.manual_seed(1)
        model = lenet.build(10)
        ds = make_dataset(256, 64, distributed=True)
        opt = Optimizer(model, ds, nn.ClassNLLCriterion())
        opt.compress_gradients = True
        opt.set_optim_method(SGD(learningrate=0.1, momentum=0.9)) \
           .set_end_when(Trigger.max_epoch(5))
        trained = opt.optimize()
        acc = eval_accuracy(trained)
        assert acc > 0.8, f"bf16-compressed training failed: acc={acc}"


class TestMeshTopology:
    def test_axes(self):
        t = MeshTopology(data=4, tensor=2)
        assert t.total() == 8
        mesh = t.build()
        assert mesh.axis_names == ("data", "tensor")
        assert mesh.devices.shape == (4, 2)

    def test_too_many_devices(self):
        with pytest.raises(AssertionError):
            MeshTopology(data=16).build()


class TestRemat:
    def test_remat_training_matches_plain(self):
        # jax.checkpoint changes memory/FLOPs, never numerics
        def run(remat):
            bt.utils.manual_seed(21)
            model = lenet.build(10)
            opt = Optimizer(model, make_dataset(128, 64),
                            nn.ClassNLLCriterion())
            opt.set_optim_method(SGD(learningrate=0.05, momentum=0.9)) \
               .set_end_when(Trigger.max_iteration(3)).set_remat(remat)
            trained = opt.optimize()
            import jax
            return [np.asarray(x) for x in
                    jax.tree_util.tree_leaves(trained.parameter_tree())]

        for a, b in zip(run(False), run(True)):
            np.testing.assert_allclose(b, a, rtol=1e-6, atol=1e-7)

    # "conv" was a policy until PR 44 (measured -7% on ResNet-50, selected
    # by no cell): it is refused as every unknown name is
    @pytest.mark.parametrize("policy", ["gibberish", "conv"])
    def test_remat_rejects_unknown_policy(self, policy):
        with pytest.raises(ValueError, match="unknown remat policy"):
            Optimizer(lenet.build(10), make_dataset(128, 64),
                      nn.ClassNLLCriterion()).set_remat(policy)

    @pytest.mark.parametrize("sync_mode", ["allreduce", "sharded"])
    def test_remat_distributed_matches_plain(self, sync_mode):
        def run(remat):
            bt.utils.manual_seed(22)
            model = lenet.build(10)
            opt = Optimizer(model, make_dataset(128, 64, distributed=True),
                            nn.ClassNLLCriterion())
            opt.sync_mode = sync_mode
            opt.set_optim_method(SGD(learningrate=0.05)) \
               .set_end_when(Trigger.max_iteration(2)).set_remat(remat)
            trained = opt.optimize()
            import jax
            return [np.asarray(x) for x in
                    jax.tree_util.tree_leaves(trained.parameter_tree())]

        for a, b in zip(run(False), run(True)):
            np.testing.assert_allclose(b, a, rtol=1e-6, atol=1e-7)


def test_transformer_tp_with_sequence_parallel_regions_trains():
    # dp=2 x tp=4 transformer with Megatron-SP regions enabled, through
    # DistriOptimizer: compiles, runs, loss finite.
    import numpy as np
    import jax.numpy as jnp
    import bigdl_tpu as bt
    from bigdl_tpu import nn
    from bigdl_tpu.dataset.base import DataSet, Sample, SampleToBatch
    from bigdl_tpu.optim import SGD, Trigger
    from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
    from bigdl_tpu.parallel.mesh import MeshTopology
    from bigdl_tpu.parallel.tensor_parallel import enable_sequence_parallel

    bt.utils.manual_seed(5)
    rng = np.random.RandomState(1)
    samples = [Sample(rng.randn(784).astype(np.float32),
                      float(rng.randint(1, 11))) for _ in range(64)]
    ds = DataSet.array(samples, distributed=True) >> SampleToBatch(32)

    topo = MeshTopology(data=2, tensor=4)
    mesh = topo.build()
    m = nn.Sequential()
    m.add(nn.Reshape((16, 49)))
    m.add(nn.Linear(49, 32))              # project to E=32, S=16
    m.add(nn.TransformerEncoderLayer(32, 4, 64, pre_norm=True))
    m.add(nn.Select(2, 1))
    m.add(nn.Linear(32, 10)).add(nn.LogSoftMax())
    tagged = enable_sequence_parallel(m, mesh)
    assert tagged == 1

    opt = DistriOptimizer(m, ds, nn.ClassNLLCriterion(), topology=topo)
    opt.set_optim_method(SGD(learningrate=0.05))
    opt.set_end_when(Trigger.max_iteration(3))
    trained = opt.optimize()
    import jax
    for leaf in jax.tree_util.tree_leaves(trained.parameter_tree()):
        assert np.isfinite(np.asarray(leaf)).all()


class TestLoopContracts:
    """The training loop dispatches one iteration at a time: exact
    per-iteration logs, stops, checkpoints and hook slots."""

    def _run(self, iters=6, trigger=None, checkpoint_dir=None):
        bt.utils.manual_seed(31)
        model = lenet.build(10)
        opt = Optimizer(model, make_dataset(512, 64), nn.ClassNLLCriterion())
        opt.set_optim_method(SGD(learningrate=0.05, momentum=0.9)) \
           .set_end_when(Trigger.max_iteration(iters))
        if trigger is not None:
            opt.set_validation(trigger, make_dataset(128, 64),
                               [Top1Accuracy()])
        if checkpoint_dir is not None:
            opt.set_checkpoint(checkpoint_dir,
                               Trigger.several_iteration(2))
        logged, validated = [], []

        class Sink:
            def __init__(self, tag, steps):
                self.tag, self.steps = tag, steps

            def add_scalar(self, tag, value, step):
                if tag == self.tag:
                    self.steps.append(step)

            def get_summary_trigger(self, name):
                return None

        opt.set_train_summary(Sink("Loss", logged))
        opt.set_validation_summary(Sink("Top1Accuracy", validated))
        opt.optimize()
        return logged, validated

    def test_every_iteration_logged_once_in_order(self):
        logged, _ = self._run()
        assert logged == [1, 2, 3, 4, 5, 6]

    def test_respects_max_iteration_exactly(self):
        logged, _ = self._run(iters=5)
        assert logged == [1, 2, 3, 4, 5]

    def test_checkpoints_at_several_iteration(self, tmp_path):
        self._run(iters=6, checkpoint_dir=str(tmp_path))
        names = sorted(p.name for p in tmp_path.iterdir())
        assert names == ["model.2", "model.4", "model.6"] + [
            f"state.{n}{tail}" for n in (2, 4, 6)
            for tail in ("", ".resume.json")]

    def test_validation_runs_in_its_hook_slot(self):
        # the trigger sees neval already advanced, so it fires after
        # iterations 1, 3 and 5 (neval 2, 4, 6)
        _, validated = self._run(iters=6,
                                 trigger=Trigger.several_iteration(2))
        assert validated == [1, 3, 5]

    def test_stateful_trigger_evaluated_once_an_iteration(self):
        seen = []

        def fn(state):
            seen.append(int(state["neval"]))
            return False

        bt.utils.manual_seed(33)
        opt = Optimizer(lenet.build(10), make_dataset(512, 64),
                        nn.ClassNLLCriterion())
        opt.set_optim_method(SGD(learningrate=0.05)) \
           .set_end_when(Trigger.max_iteration(5))
        opt.set_validation(Trigger(fn), make_dataset(64, 64),
                           [Top1Accuracy()])
        opt.optimize()
        # once an iteration, in order, and once more at the epoch's end
        assert seen == [2, 3, 4, 5, 6, 6], seen

    def test_one_step_program(self):
        """A second step program cannot come back unnoticed: the loop
        compiles `train.step` once and `train.forward` for validation,
        and nothing else under `train.`."""
        from bigdl_tpu.telemetry import (MetricsRegistry, get_registry,
                                         instruments, set_registry)
        assert not hasattr(Optimizer, "set_steps_per_dispatch")
        prev = set_registry(MetricsRegistry())
        try:
            self._run(iters=3, trigger=Trigger.several_iteration(3))
            compiles = {lv[0]: c.value for lv, c in instruments(
                get_registry()).compiles_total.children()
                if lv[0].startswith("train.")}
        finally:
            set_registry(prev)
        assert set(compiles) == {"train.step", "train.forward"}, compiles
        assert compiles["train.step"] == 1
