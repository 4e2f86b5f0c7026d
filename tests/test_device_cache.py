"""DeviceCachedDataSet: on-device dataset cache (PERF.md round 3).

Semantics under test: sample-level reshuffle per epoch (reference
CachedDistriDataSet's "shuffle = reshuffle indexes only",
``DataSet.scala:292-299``), exact batch contents vs the host path, one
materialization, terminal-stage contract, and end-to-end training parity.
"""

import numpy as np
import pytest

import bigdl_tpu as bt
from bigdl_tpu import nn
from bigdl_tpu.dataset import DeviceCachedDataSet, Sample, SampleToBatch
from bigdl_tpu.dataset.base import DataSet
from bigdl_tpu.models import lenet
from bigdl_tpu.optim import Optimizer, SGD, Trigger


def _samples(n, shape=(4,), classes=2):
    rng = np.random.default_rng(0)
    return [Sample(rng.normal(0, 1, shape).astype(np.float32),
                   float(rng.integers(1, classes + 1))) for i in range(n)]


def test_eval_batches_match_host_path():
    samples = _samples(10)
    cached = DeviceCachedDataSet(DataSet.array(samples), batch_size=4)
    host = DataSet.array(samples) >> SampleToBatch(4)
    a = list(cached.data(train=False))
    b = list(host.data(train=False))
    assert len(a) == len(b) == 2  # drop-remainder parity
    for ca, cb in zip(a, b):
        np.testing.assert_array_equal(np.asarray(ca.data), cb.data)
        np.testing.assert_array_equal(np.asarray(ca.labels), cb.labels)


def test_train_epoch_is_sample_level_permutation():
    samples = _samples(8, shape=(1,))
    ds = DeviceCachedDataSet(DataSet.array(samples), batch_size=4)
    bt.utils.manual_seed(7)
    epoch1 = np.concatenate([np.asarray(b.data).ravel()
                             for b in ds.data(train=True)])
    epoch2 = np.concatenate([np.asarray(b.data).ravel()
                             for b in ds.data(train=True)])
    all_feats = np.concatenate([s.feature for s in samples])
    # every sample appears exactly once per epoch...
    np.testing.assert_allclose(np.sort(epoch1), np.sort(all_feats), rtol=1e-6)
    # ...and batch composition changes between epochs (sample-level shuffle)
    assert not np.array_equal(epoch1, epoch2)


def test_materializes_once_and_serves_many_epochs():
    calls = {"n": 0}

    class CountingDataSet(DataSet.array(_samples(8)).__class__):
        def data(self, train):
            calls["n"] += 1
            return super().data(train)

    base = CountingDataSet(_samples(8))
    ds = DeviceCachedDataSet(base, batch_size=4)
    for _ in range(3):
        list(ds.data(train=True))
    assert calls["n"] == 1, "base dataset must be read exactly once"


def test_terminal_stage_and_validation():
    ds = DeviceCachedDataSet(DataSet.array(_samples(8)), batch_size=4)
    with pytest.raises(TypeError):
        ds.transform(SampleToBatch(2))
    with pytest.raises(ValueError):
        list(DeviceCachedDataSet(DataSet.array(_samples(2)),
                                 batch_size=4).data(train=False))
    with pytest.raises(ValueError):
        DeviceCachedDataSet(DataSet.array(_samples(4)), batch_size=0)


def test_caches_image_pipeline_types():
    # the image transformers yield LabeledImage (array under .data, not
    # .feature) — the cache must accept the standard MNIST chain (caught on
    # the real chip by the round-3 verify drive)
    from bigdl_tpu.dataset import mnist
    from bigdl_tpu.dataset.image import BytesToGreyImg, GreyImgNormalizer
    raw = (DataSet.array(mnist.synthetic(16)) >> BytesToGreyImg(28, 28)
           >> GreyImgNormalizer(33., 78.))
    ds = DeviceCachedDataSet(raw, batch_size=8)
    batches = list(ds.data(train=False))
    assert [b.size() for b in batches] == [8, 8]
    assert batches[0].data.shape == (8, 28, 28, 1)


def test_rejects_stochastic_stage_below_cache():
    # freezing a random augmentation at materialization is silent model
    # damage -> hard error (the stochastic flag on Transformer)
    from bigdl_tpu.dataset import mnist
    from bigdl_tpu.dataset.image import BytesToGreyImg, HFlip
    raw = DataSet.array(mnist.synthetic(16)) >> BytesToGreyImg(28, 28) \
        >> HFlip(0.5)
    with pytest.raises(ValueError, match="stochastic"):
        list(DeviceCachedDataSet(raw, batch_size=8).data(train=False))


def test_shape1_labels_squeezed_like_host_path():
    # SampleToBatch squeezes (N,1) labels to (N,); the cache must match or
    # ClassNLLCriterion breaks on previously-working datasets
    samples = [Sample(np.ones((4,), np.float32), np.asarray([float(i % 2 + 1)]))
               for i in range(8)]
    cached = next(DeviceCachedDataSet(DataSet.array(samples), batch_size=8)
                  .data(train=False))
    host = next((DataSet.array(samples) >> SampleToBatch(8))
                .data(train=False))
    assert cached.labels.shape == host.labels.shape == (8,)


def test_cast_dtype_halves_cache():
    import jax.numpy as jnp
    ds = DeviceCachedDataSet(DataSet.array(_samples(8)), batch_size=4,
                             cast_dtype="bfloat16")
    batch = next(ds.data(train=False))
    assert batch.data.dtype == jnp.bfloat16


def test_training_through_device_cache_matches_host_path(monkeypatch):
    # Same seed, same model init, same batches -> identical trained params
    # whether batches come from the device cache or the host collate path.
    # Shuffles are pinned to identity (the two paths draw from the RNG
    # differently; sample-level shuffle semantics are asserted above) so
    # any divergence here is a COMPUTE-path difference.
    from bigdl_tpu.dataset.base import LocalDataSet
    monkeypatch.setattr(LocalDataSet, "shuffle", lambda self: None)
    monkeypatch.setattr(
        DeviceCachedDataSet, "shuffle",
        lambda self: setattr(self, "_perm",
                             np.arange(self.size(), dtype=np.int32)))

    def run(cached):
        bt.utils.manual_seed(11)
        rng = np.random.default_rng(3)
        samples = [Sample(rng.normal(0, 1, (28, 28, 1)).astype(np.float32),
                          float(rng.integers(1, 11))) for _ in range(64)]
        if cached:
            ds = DeviceCachedDataSet(DataSet.array(samples), batch_size=32)
        else:
            ds = DataSet.array(samples) >> SampleToBatch(32)
        model = lenet.build(10)
        opt = Optimizer(model, ds, nn.ClassNLLCriterion())
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.set_end_when(Trigger.max_iteration(4))
        trained = opt.optimize()
        import jax
        return [np.asarray(x) for x in
                jax.tree_util.tree_leaves(trained.parameter_tree())]

    for a, b in zip(run(False), run(True)):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-6)


class TestShardedCache:
    """Sharded device cache under DistriOptimizer (8-device virtual mesh):
    per-shard reshuffle (reference CachedDistriDataSet's per-partition
    semantics), shard_map-local gathers, factory routing."""

    def _samples(self, n):
        rng = np.random.default_rng(9)
        return [Sample(rng.normal(0, 1, (28, 28, 1)).astype(np.float32),
                       float(rng.integers(1, 11))) for _ in range(n)]

    def test_routes_to_distri_and_trains(self):
        from bigdl_tpu.dataset import mnist
        from bigdl_tpu.dataset.image import (BytesToGreyImg,
                                             GreyImgNormalizer)
        from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
        bt.utils.manual_seed(41)
        raw = (DataSet.array(mnist.synthetic(512), distributed=True)
               >> BytesToGreyImg(28, 28) >> GreyImgNormalizer(33., 78.))
        ds = DeviceCachedDataSet(raw, batch_size=64)
        opt = Optimizer(lenet.build(10), ds, nn.ClassNLLCriterion())
        assert isinstance(opt, DistriOptimizer)
        opt.set_optim_method(SGD(learningrate=0.1, momentum=0.9))
        opt.set_end_when(Trigger.max_epoch(4))
        trained = opt.optimize()
        from bigdl_tpu.optim import Top1Accuracy
        acc = trained.evaluate(ds, [Top1Accuracy()])[0][0].result()[0]
        assert acc > 0.5, f"sharded-cache training failed: acc={acc}"

    def test_epoch_is_within_shard_permutation(self):
        from bigdl_tpu.parallel.mesh import MeshTopology
        mesh = MeshTopology(data=4).build()
        samples = [Sample(np.full((2,), i, np.float32), 1.0)
                   for i in range(16)]
        ds = DeviceCachedDataSet(DataSet.array(samples), batch_size=8)
        ds.set_mesh(mesh, "data")
        bt.utils.manual_seed(43)
        feats = np.concatenate([np.asarray(b.data)[:, 0]
                                for b in ds.data(train=True)])
        # every sample exactly once
        np.testing.assert_array_equal(np.sort(feats), np.arange(16))
        # batch layout: rows grouped per shard (B/d from each shard), and
        # each shard's rows drawn only from that shard's quarter
        for b in range(2):
            batch = feats[b * 8:(b + 1) * 8].reshape(4, 2)
            for s in range(4):
                assert set(batch[s] // 4) == {s}, (b, s, batch)

    def test_eval_covers_every_record_once(self):
        from bigdl_tpu.parallel.mesh import MeshTopology
        mesh = MeshTopology(data=4).build()
        samples = [Sample(np.full((2,), i, np.float32), 1.0)
                   for i in range(16)]
        ds = DeviceCachedDataSet(DataSet.array(samples), batch_size=8)
        ds.set_mesh(mesh, "data")
        feats = np.concatenate([np.asarray(b.data)[:, 0]
                                for b in ds.data(train=False)])
        np.testing.assert_array_equal(np.sort(feats), np.arange(16))

    def test_rejects_indivisible_batch(self):
        from bigdl_tpu.parallel.mesh import MeshTopology
        mesh = MeshTopology(data=8).build()
        ds = DeviceCachedDataSet(DataSet.array(self._samples(64)),
                                 batch_size=12)  # 12 % 8 != 0
        ds.set_mesh(mesh, "data")
        with pytest.raises(ValueError, match="data-axis"):
            list(ds.data(train=False))

    def test_set_mesh_after_materialize_rejected(self):
        from bigdl_tpu.parallel.mesh import MeshTopology
        ds = DeviceCachedDataSet(DataSet.array(self._samples(16)),
                                 batch_size=8)
        list(ds.data(train=False))
        with pytest.raises(RuntimeError, match="materialized"):
            ds.set_mesh(MeshTopology(data=4).build(), "data")
