"""Sharded checkpointing (per-process shard files, resharding restore) —
VERDICT round-4 weak #5 / next-round #4. Contract being replaced:
``optim/DistriOptimizer.scala:378-400`` (driver reassembles + serializes).

Library level: save a tree sharded on one mesh, restore onto a different
mesh/specs, bit-exact. Optimizer level: a run checkpointed with
``set_checkpoint(sharded=True)`` resumes into a DIFFERENT sync mode /
placement and finishes with the same weights as an uninterrupted run.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import bigdl_tpu as bt
from bigdl_tpu import nn
from bigdl_tpu.dataset.base import MiniBatch
from bigdl_tpu.optim import SGD, Trigger
from bigdl_tpu.parallel import MeshTopology
from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
from bigdl_tpu.utils.sharded_checkpoint import (is_sharded_checkpoint,
                                                load_sharded, save_sharded)


def _mesh(shape, names):
    devs = np.array(jax.devices()[:int(np.prod(shape))]).reshape(shape)
    return Mesh(devs, names)


class TestLibraryRoundTrip:
    def test_reshard_2x4_to_4x2(self, tmp_path):
        """The headline contract: save on a 2x4 mesh, restore on 4x2 —
        shard boundaries differ on both axes; assembly must be exact."""
        m_save = _mesh((2, 4), ("a", "b"))
        m_load = _mesh((4, 2), ("a", "b"))
        rng = np.random.RandomState(0)
        w = rng.randn(16, 12).astype(np.float32)
        v = rng.randn(8).astype(np.float32)
        tree = {
            "w": jax.device_put(w, NamedSharding(m_save, P("a", "b"))),
            "v": jax.device_put(v, NamedSharding(m_save, P("a"))),
            "scalar": jax.device_put(jnp.float32(3.5),
                                     NamedSharding(m_save, P())),
        }
        save_sharded(str(tmp_path / "ck"), tree)
        assert is_sharded_checkpoint(str(tmp_path / "ck"))
        out = load_sharded(str(tmp_path / "ck"), {
            "w": NamedSharding(m_load, P("b", "a")),   # transposed axes too
            "v": NamedSharding(m_load, P("b")),
            "scalar": NamedSharding(m_load, P()),
        })
        np.testing.assert_array_equal(np.asarray(out["w"]), w)
        np.testing.assert_array_equal(np.asarray(out["v"]), v)
        assert float(out["scalar"]) == 3.5
        assert out["w"].sharding.spec == P("b", "a")

    def test_restore_to_host(self, tmp_path):
        m = _mesh((8,), ("d",))
        w = np.arange(64, dtype=np.float32).reshape(8, 8)
        tree = {"w": jax.device_put(w, NamedSharding(m, P("d")))}
        save_sharded(str(tmp_path / "ck"), tree)
        out = load_sharded(str(tmp_path / "ck"), {"w": None})
        assert isinstance(out["w"], np.ndarray)
        np.testing.assert_array_equal(out["w"], w)

    def test_replicated_leaf_stored_once(self, tmp_path):
        """replica_id==0 dedup: a replicated leaf must appear in exactly
        one slab across all shard files (no 8x blowup)."""
        m = _mesh((8,), ("d",))
        tree = {"w": jax.device_put(np.ones((4, 4), np.float32),
                                    NamedSharding(m, P()))}
        save_sharded(str(tmp_path / "ck"), tree)
        slabs = []
        for f in os.listdir(tmp_path / "ck"):
            if f.endswith(".npz"):
                with np.load(tmp_path / "ck" / f) as z:
                    slabs += list(z.files)
        assert len(slabs) == 1

    def test_incomplete_checkpoint_raises(self, tmp_path):
        m = _mesh((8,), ("d",))
        tree = {"w": jax.device_put(np.ones((8, 4), np.float32),
                                    NamedSharding(m, P("d")))}
        save_sharded(str(tmp_path / "ck"), tree)
        # simulate a lost process file by deleting one slab's worth: rewrite
        # the npz with half its members dropped
        fname = next(f for f in os.listdir(tmp_path / "ck")
                     if f.endswith(".npz"))
        full = tmp_path / "ck" / fname
        with np.load(full) as z:
            kept = {k: z[k] for k in list(z.files)[:len(z.files) // 2]}
        np.savez(full, **kept)
        with pytest.raises(ValueError, match="do not cover"):
            load_sharded(str(tmp_path / "ck"), {"w": None})

    def test_host_leaf_and_numpy_tree(self, tmp_path):
        tree = {"a": np.arange(6).reshape(2, 3), "b": 7}
        save_sharded(str(tmp_path / "ck"), tree)
        out = load_sharded(str(tmp_path / "ck"), {"a": None, "b": None})
        np.testing.assert_array_equal(out["a"], tree["a"])
        assert int(out["b"]) == 7

    def test_stale_shard_from_wider_save_is_invisible(self, tmp_path):
        """The ADVICE stale-shard overwrite hazard: a snapshot dir reused
        by a save with FEWER processes must not resurrect slabs from the
        earlier, wider save. Simulated by planting the wider run's extra
        shard file (shard-00001.npz with stale values at the same
        offsets), then re-saving with this 1-process run: the manifest
        now names only shard-00000, process 0 deletes the foreign file,
        and restore sees only fresh data."""
        import shutil
        ck = tmp_path / "ck"
        stale = {"w": np.full((8, 4), 111.0, np.float32)}
        save_sharded(str(ck), stale)
        # the "second process" of an imaginary wider save left this behind
        shutil.copy(ck / "shard-00000.npz", ck / "shard-00001.npz")
        fresh = {"w": np.full((8, 4), 222.0, np.float32)}
        save_sharded(str(ck), fresh)
        assert not (ck / "shard-00001.npz").exists()  # stale file cleared
        out = load_sharded(str(ck), {"w": None})
        np.testing.assert_array_equal(out["w"], fresh["w"])

    def test_manifest_names_shards_and_restricts_reads(self, tmp_path):
        """Format-2 manifests pin the participating shard files; a
        planted foreign shard-*.npz (even one that survives the stale
        clear, e.g. copied in AFTER the save) is not read."""
        import json
        ck = tmp_path / "ck"
        save_sharded(str(ck), {"w": np.arange(8, dtype=np.float32)})
        with open(ck / "manifest.json") as f:
            manifest = json.load(f)
        assert manifest["format"] == 2
        assert manifest["shards"] == ["shard-00000.npz"]
        # plant a stale shard after the save: same member names, wrong data
        import shutil
        shutil.copy(ck / "shard-00000.npz", ck / "shard-00099.npz")
        with open(ck / "shard-00000.npz", "rb") as f:
            good = f.read()
        out = load_sharded(str(ck), {"w": None})
        np.testing.assert_array_equal(out["w"],
                                      np.arange(8, dtype=np.float32))
        with open(ck / "shard-00000.npz", "rb") as f:
            assert f.read() == good  # untouched

    def test_missing_manifest_shard_raises(self, tmp_path):
        ck = tmp_path / "ck"
        save_sharded(str(ck), {"w": np.arange(8, dtype=np.float32)})
        os.unlink(ck / "shard-00000.npz")
        with pytest.raises(ValueError, match="incomplete"):
            load_sharded(str(ck), {"w": None})

    def test_format1_manifest_still_loads(self, tmp_path):
        """Back-compat: a pre-fix snapshot (bare leaves-dict manifest, no
        shard list) restores via the glob path."""
        import json
        ck = tmp_path / "ck"
        save_sharded(str(ck), {"w": np.arange(8, dtype=np.float32)})
        with open(ck / "manifest.json") as f:
            manifest = json.load(f)
        with open(ck / "manifest.json", "w") as f:
            json.dump(manifest["leaves"], f)  # rewrite as format 1
        out = load_sharded(str(ck), {"w": None})
        np.testing.assert_array_equal(out["w"],
                                      np.arange(8, dtype=np.float32))


def _fixed_batches(n_batches=4, batch=32, dim=6, classes=3, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(batch, dim).astype(np.float32),
             rng.randint(1, classes + 1, batch).astype(np.float32))
            for _ in range(n_batches)]


class _FixedDataSet:
    def __init__(self, batches):
        self.batches = batches

    def data(self, train):
        for x, y in self.batches:
            yield MiniBatch(x, y)

    def size(self):
        return sum(b[0].shape[0] for b in self.batches)

    def shuffle(self):
        pass

    def is_distributed(self):
        return False


def _mk_model(seed=11):
    bt.utils.manual_seed(seed)
    m = nn.Sequential().add(nn.Linear(6, 8)).add(nn.Tanh())
    m.add(nn.Linear(8, 3)).add(nn.LogSoftMax())
    return m


def _flat(params):
    return np.concatenate([np.asarray(l).ravel()
                           for l in jax.tree_util.tree_leaves(params)])


class TestOptimizerShardedResume:
    @pytest.mark.parametrize("save_mode,resume_mode", [
        ("fsdp", "fsdp"),
        ("fsdp", "allreduce"),     # resharding restore across layouts
        ("allreduce", "fsdp"),
    ])
    def test_resume_matches_uninterrupted(self, tmp_path, save_mode,
                                          resume_mode):
        batches = _fixed_batches()
        mk = lambda: SGD(learningrate=0.1, momentum=0.9)

        # uninterrupted: 2 epochs
        m_ref = _mk_model()
        opt = DistriOptimizer(m_ref, _FixedDataSet(batches),
                              nn.ClassNLLCriterion(),
                              topology=MeshTopology.data_parallel(),
                              sync_mode=save_mode)
        opt.set_optim_method(mk()).set_end_when(Trigger.max_epoch(2))
        ref = _flat(opt.optimize().parameter_tree())

        # interrupted: 1 epoch + sharded checkpoint, resume for epoch 2
        m_a = _mk_model()
        opt_a = DistriOptimizer(m_a, _FixedDataSet(batches),
                                nn.ClassNLLCriterion(),
                                topology=MeshTopology.data_parallel(),
                                sync_mode=save_mode)
        opt_a.set_optim_method(mk()).set_end_when(Trigger.max_epoch(1))
        opt_a.set_checkpoint(str(tmp_path), Trigger.every_epoch(),
                             sharded=True)
        opt_a.optimize()
        model_dir = tmp_path / "model.5"  # 4 batches/epoch -> neval 5
        assert is_sharded_checkpoint(str(model_dir))

        m_b = _mk_model(seed=99)  # different init: must be overwritten
        opt_b = DistriOptimizer(m_b, _FixedDataSet(batches),
                                nn.ClassNLLCriterion(),
                                topology=MeshTopology.data_parallel(),
                                sync_mode=resume_mode)
        opt_b.set_optim_method(mk()).set_end_when(Trigger.max_epoch(2))
        opt_b.resume(str(model_dir), str(tmp_path / "state.5"))
        resumed = _flat(opt_b.optimize().parameter_tree())

        np.testing.assert_allclose(resumed, ref, rtol=1e-5, atol=1e-6)

    def test_zero1_sharded_checkpoint_refused(self, tmp_path):
        opt = DistriOptimizer(_mk_model(), _FixedDataSet(_fixed_batches()),
                              nn.ClassNLLCriterion(),
                              topology=MeshTopology.data_parallel(),
                              sync_mode="sharded")
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.set_end_when(Trigger.max_epoch(1))
        opt.set_checkpoint(str(tmp_path), Trigger.every_epoch(),
                           sharded=True)
        with pytest.raises(ValueError, match="fsdp"):
            opt.optimize()
