"""Core tests: Table, Engine, module protocol, functional apply, flatten.

Reference analogues: ``$T/utils/TableSpec``, ``EngineSpec``, module protocol
behaviour from ``$T/nn/`` specs.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu as bt
from bigdl_tpu import nn
from bigdl_tpu.utils.table import T, Table


class TestTable:
    def test_builder_and_1_based(self):
        t = T(10, 20, 30)
        assert t[1] == 10 and t[3] == 30
        assert t.length() == 3
        assert list(t) == [10, 20, 30]

    def test_insert_and_kwargs(self):
        t = T(learningRate=0.1)
        t.insert(5)
        assert t[1] == 5 and t["learningRate"] == 0.1

    def test_pytree(self):
        t = T(jnp.ones(3), jnp.zeros(2))
        doubled = jax.tree_util.tree_map(lambda x: x * 2, t)
        assert isinstance(doubled, Table)
        assert float(doubled[1][0]) == 2.0


class TestEngine:
    def test_topology(self):
        bt.Engine.init()
        assert bt.Engine.device_count() == 8  # virtual CPU mesh from conftest
        mesh = bt.Engine.default_mesh()
        assert mesh.axis_names == ("data",)
        assert mesh.devices.size == 8


class TestModuleProtocol:
    def test_parameter_tree_roundtrip(self):
        m = nn.Sequential().add(nn.Linear(4, 3)).add(nn.ReLU()).add(nn.Linear(3, 2))
        tree = m.parameter_tree()
        assert tree["0"]["weight"].shape == (3, 4)
        zeroed = jax.tree_util.tree_map(jnp.zeros_like, tree)
        m.load_parameter_tree(zeroed)
        assert float(jnp.sum(jnp.abs(m[0].weight))) == 0.0

    def test_functional_apply_pure(self):
        m = nn.Linear(4, 2)
        x = jnp.ones((3, 4))
        params = m.parameter_tree()
        before = np.asarray(m.weight)
        out, _ = nn.functional_apply(
            m, jax.tree_util.tree_map(jnp.zeros_like, params), {}, x)
        # module state untouched after functional apply with other params
        assert np.allclose(np.asarray(m.weight), before)
        assert float(jnp.sum(jnp.abs(out))) == 0.0

    def test_get_parameters_flat(self):
        m = nn.Linear(4, 2)
        flat, unravel = m.get_parameters()
        assert flat.shape == (4 * 2 + 2,)
        tree = unravel(flat)
        assert np.allclose(tree["weight"], m.weight)

    def test_forward_backward(self):
        m = nn.Linear(3, 3)
        x = jnp.ones((2, 3))
        out = m.forward(x)
        g = m.backward(x, jnp.ones_like(out))
        # dL/dx = 1^T W
        expected = jnp.sum(m.weight, axis=0)
        assert np.allclose(np.asarray(g), np.tile(expected, (2, 1)), atol=1e-5)

    def test_training_mode_propagates(self):
        m = nn.Sequential().add(nn.Dropout(0.5)).add(nn.Linear(2, 2))
        m.evaluate_mode()
        assert not m[0].training
        m.training_mode()
        assert m[0].training

    def test_named_lookup(self):
        m = nn.Sequential().add(nn.Linear(2, 2).set_name("fc1"))
        assert m.find_module("fc1") is m[0]

    def test_jit_apply_caches(self):
        m = nn.Sequential().add(nn.Linear(4, 4)).add(nn.Tanh())
        fn = nn.jit_apply(m)
        p, b = m.parameter_tree(), m.buffer_tree()
        x = jnp.ones((2, 4))
        out1, _ = fn(p, b, x, training=False)
        out2, _ = fn(p, b, x, training=False)
        assert np.allclose(out1, out2)


class TestGraph:
    def test_dag_multi_input(self):
        i1 = nn.Input().inputs()
        i2 = nn.Input().inputs()
        a = nn.Linear(3, 4).inputs(i1)
        b = nn.Linear(5, 4).inputs(i2)
        s = nn.CAddTable().inputs(a, b)
        out = nn.ReLU().inputs(s)
        g = nn.Graph([i1, i2], out)
        y = g.forward(T(jnp.ones((2, 3)), jnp.ones((2, 5))))
        assert y.shape == (2, 4)

    def test_cycle_detection(self):
        i1 = nn.Input().inputs()
        a = nn.Linear(3, 3)
        n1 = a.inputs(i1)
        n2 = nn.ReLU().inputs(n1)
        n1.prev.append(n2)  # forge a cycle
        with pytest.raises(ValueError, match="cycle"):
            nn.Graph(i1, n2)

    def test_fan_out_gradient(self):
        # One node feeding two branches: autodiff must accumulate.
        i1 = nn.Input().inputs()
        shared = nn.Linear(3, 3).inputs(i1)
        b1 = nn.ReLU().inputs(shared)
        b2 = nn.Tanh().inputs(shared)
        out = nn.CAddTable().inputs(b1, b2)
        g = nn.Graph(i1, out)
        x = jnp.ones((2, 3))
        gi = g.backward(x, jnp.ones((2, 3)))
        assert gi.shape == (2, 3)
        assert float(jnp.sum(jnp.abs(gi))) > 0


class TestFileIO:
    def test_save_load_roundtrip(self, tmp_path):
        obj = {"params": {"w": jnp.arange(6.0).reshape(2, 3)}, "epoch": 3}
        p = str(tmp_path / "ckpt" / "model")
        bt.utils.save(obj, p)
        back = bt.utils.load(p)
        assert back["epoch"] == 3
        assert np.allclose(back["params"]["w"], np.arange(6.0).reshape(2, 3))

    def test_bad_magic(self, tmp_path):
        p = tmp_path / "junk"
        p.write_bytes(b"not a checkpoint")
        with pytest.raises(ValueError):
            bt.utils.load(str(p))


class TestDirectedGraph:
    """reference ``$T/utils/DirectedGraphSpec``: traversal orders, topo sort,
    cycle detection, edge builder."""

    def _diamond(self):
        from bigdl_tpu.utils.digraph import DirectedGraph, Node
        a, b, c, d = Node("a"), Node("b"), Node("c"), Node("d")
        a >> b >> d
        a >> c >> d
        return DirectedGraph(a), (a, b, c, d)

    def test_bfs_dfs_size(self):
        g, (a, b, c, d) = self._diamond()
        assert g.size() == 4 and g.edges() == 4
        bfs = [n.element for n in g.bfs()]
        assert bfs[0] == "a" and set(bfs) == {"a", "b", "c", "d"}
        dfs = [n.element for n in g.dfs()]
        assert dfs[0] == "a" and len(dfs) == 4

    def test_topology_sort_respects_edges(self):
        g, (a, b, c, d) = self._diamond()
        order = [n.element for n in g.topology_sort()]
        assert order.index("a") < order.index("b") < order.index("d")
        assert order.index("a") < order.index("c") < order.index("d")

    def test_cycle_detection(self):
        from bigdl_tpu.utils.digraph import DirectedGraph, Node
        a, b = Node(1), Node(2)
        a >> b
        b >> a
        with pytest.raises(ValueError, match="cycle"):
            DirectedGraph(a).topology_sort()

    def test_reverse_graph(self):
        from bigdl_tpu.utils.digraph import DirectedGraph, Node
        a, b = Node(1), Node(2)
        a >> b
        rev = DirectedGraph(b, reverse=True)
        assert [n.element for n in rev.bfs()] == [2, 1]


class TestEngineEnvCheck:
    """reference ``Engine.checkSparkContext`` / required-conf verification
    (``utils/Engine.scala:269-293``)."""

    def test_complaints_and_strict(self, monkeypatch):
        from bigdl_tpu.utils.engine import Engine
        monkeypatch.delenv("BIGDL_TPU_DISABLE_ENV_CHECK", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("OMP_NUM_THREADS", "16")
        problems = Engine.check_env()
        assert len(problems) == 1 and "OMP_NUM_THREADS" in problems[0]
        with pytest.raises(RuntimeError, match="environment check"):
            Engine.check_env(strict=True)

    def test_clean_env_passes(self, monkeypatch):
        from bigdl_tpu.utils.engine import Engine
        monkeypatch.delenv("BIGDL_TPU_DISABLE_ENV_CHECK", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("OMP_NUM_THREADS", "1")
        assert Engine.check_env(strict=True) == []

    def test_disable_switch(self, monkeypatch):
        from bigdl_tpu.utils.engine import Engine
        monkeypatch.setenv("OMP_NUM_THREADS", "16")
        monkeypatch.setenv("BIGDL_TPU_DISABLE_ENV_CHECK", "1")
        assert Engine.check_env(strict=True) == []


class TestCompileCacheRule:
    """One rule, one setter (``utils/engine.py::compile_cache_dir``)."""

    def test_unset_env_gives_the_same_in_checkout_path(self, monkeypatch):
        from bigdl_tpu.utils import engine
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        first = engine.compile_cache_dir()
        assert first == os.path.join(repo, ".jax_cache")
        assert engine.compile_cache_dir() == first
        assert jax.config.jax_compilation_cache_dir == first

    def test_set_env_leaves_the_config_alone(self, monkeypatch):
        from bigdl_tpu.utils import engine
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a, **k: calls.append(a))
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert engine.compile_cache_dir() == "/elsewhere"
        assert calls == []


class TestRandomGeneratorDistributions:
    """reference ``utils/RandomGenerator.scala``: uniform/normal/exponential/
    cauchy/logNormal/geometric/bernoulli streams — statistical sanity plus
    seed determinism."""

    def test_statistics(self):
        from bigdl_tpu.utils.rng import RandomGenerator
        rng = RandomGenerator(7)
        n = 20_000
        u = rng.uniform(2.0, 5.0, n)
        assert 2.0 <= u.min() and u.max() < 5.0
        assert abs(u.mean() - 3.5) < 0.05
        g = rng.normal(1.0, 2.0, n)
        assert abs(g.mean() - 1.0) < 0.06 and abs(g.std() - 2.0) < 0.06
        e = rng.exponential(2.0, n)
        assert e.min() >= 0 and abs(e.mean() - 0.5) < 0.03
        c = rng.cauchy(0.0, 1.0, n)
        assert abs(np.median(c)) < 0.05  # mean undefined; median is the pin
        ln = rng.log_normal(1.0, 0.5, n)
        assert ln.min() > 0
        geo = rng.geometric(0.25, n)
        assert geo.min() >= 1 and abs(geo.mean() - 4.0) < 0.2
        b = rng.bernoulli(0.3, n)
        assert set(np.unique(b)) <= {0.0, 1.0}
        assert abs(b.mean() - 0.3) < 0.02

    def test_seed_determinism_and_randperm(self):
        from bigdl_tpu.utils.rng import RandomGenerator
        a = RandomGenerator(123).normal(0, 1, 16)
        b = RandomGenerator(123).normal(0, 1, 16)
        np.testing.assert_array_equal(a, b)
        p = RandomGenerator(5).randperm(50)
        assert sorted(p.tolist()) == list(range(1, 51)) or \
            sorted(p.tolist()) == list(range(50))
