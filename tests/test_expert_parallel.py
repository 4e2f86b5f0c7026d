"""MoE / expert-parallel tests on the 8-device virtual mesh. The reference's
``MixtureTable`` is single-node gating; ``MoE`` extends it to distributed
expert parallelism (SURVEY §2.5 "Expert parallelism: ABSENT")."""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import bigdl_tpu as bt
from bigdl_tpu import nn
from bigdl_tpu.nn.module import functional_apply
from bigdl_tpu.parallel.expert import MoE, expert_param_specs, inject_loss
from bigdl_tpu.parallel.mesh import MeshTopology

logging.getLogger("bigdl_tpu.optim").setLevel(logging.WARNING)


def _rand(*shape):
    return jnp.asarray(np.random.randn(*shape).astype(np.float32))


def gshard_reference(p, x, k, capacity_factor, aux_loss_weight=0.0):
    """The capacity-limited top-k layer in the dense GShard formulation,
    plain ``jax.numpy`` in float32 and independent of ``MoE``: (y (T, D),
    aux loss) for tokens ``x`` (T, D) and the layer's parameter tree
    ``p``. Slots come from running counts (a token's position among the
    earlier tokens and earlier rounds routed to the same expert), and
    dispatch and combine are one-hot (T, E, C) masks contracted by
    einsums: O(T E C) memory, which is why it is a reference and not a
    path. The kept gate weights are renormalised to sum 1 a token and
    rescaled by the full top-k probability mass, drops included."""
    t, e = x.shape[0], p["gate_weight"].shape[1]
    capacity = min(t, max(1, int(np.ceil(t / e * capacity_factor * k))))
    probs = jax.nn.softmax(x @ p["gate_weight"], axis=-1)    # (T, E)
    masked, fill = probs, jnp.zeros((e,))
    topk_mask = jnp.zeros_like(probs)
    dispatch = jnp.zeros((t, e, capacity))
    weights = jnp.zeros((t, e, capacity))
    for _ in range(k):
        onehot = jax.nn.one_hot(jnp.argmax(masked, axis=-1), e)
        topk_mask = topk_mask + onehot
        pos = jnp.cumsum(onehot, axis=0) - onehot + fill[None, :]
        slot = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)
        kept = slot < capacity
        mask = (onehot[:, :, None]
                * jax.nn.one_hot(slot, capacity)[:, None, :]
                * kept[:, None, None])
        dispatch = dispatch + mask
        weights = weights + mask * jnp.sum(probs * onehot,
                                           axis=-1)[:, None, None]
        fill = fill + jnp.sum(onehot * kept[:, None], axis=0)
        masked = masked * (1.0 - onehot)
    coef = (jnp.sum(probs * topk_mask, axis=-1)
            / jnp.maximum(jnp.sum(weights, axis=(1, 2)), 1e-9))
    xe = jnp.einsum("tec,td->ecd", dispatch, x)
    hid = jax.nn.gelu(jnp.einsum("ecd,edh->ech", xe, p["w1"])
                      + p["b1"][:, None, :])
    ye = jnp.einsum("ech,ehd->ecd", hid, p["w2"]) + p["b2"][:, None, :]
    y = jnp.einsum("tec,ecd->td", weights * coef[:, None, None], ye)
    aux = (e * jnp.sum(jnp.mean(topk_mask / k, axis=0)
                       * jnp.mean(probs, axis=0)) * aux_loss_weight)
    return y, aux


class TestMoELocal:
    def test_output_shape_and_determinism(self):
        m = MoE(16, 32, n_experts=4, k=2).evaluate_mode()
        x = _rand(3, 7, 16)
        out = m.forward(x)
        assert out.shape == (3, 7, 16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(m.forward(x)),
                                   rtol=0, atol=0)

    def test_k1_matches_manual_route(self):
        # With k=1 and generous capacity, each token's output must equal
        # gate_prob * FFN_expert(token) for its argmax expert.
        m = MoE(8, 16, n_experts=2, k=1, capacity_factor=4.0).evaluate_mode()
        x = _rand(5, 8)
        out = np.asarray(m.forward(x))
        probs = np.asarray(jax.nn.softmax(x @ m.gate_weight, axis=-1))
        pick = probs.argmax(-1)
        for t in range(5):
            e = pick[t]
            h = np.asarray(jax.nn.gelu(x[t] @ m.w1[e] + m.b1[e]))
            y = h @ np.asarray(m.w2[e]) + np.asarray(m.b2[e])
            np.testing.assert_allclose(out[t], probs[t, e] * y,
                                       rtol=1e-4, atol=1e-4)

    def test_capacity_drops_tokens(self):
        # capacity 1 with many tokens: most tokens get zero output.
        m = MoE(8, 8, n_experts=2, k=1, capacity_factor=0.01).evaluate_mode()
        x = _rand(16, 8)
        out = np.asarray(m.forward(x))
        zero_rows = (np.abs(out).max(axis=-1) < 1e-7).sum()
        assert zero_rows >= 14  # 2 experts x capacity 1 served at most 2

    @pytest.mark.parametrize("dispatch", ["scatter", "einsum"])
    def test_the_retired_dispatches_are_refused(self, dispatch):
        # options until PR 44: duplicates of the sort path that no cell
        # and no caller but an A/B script selected
        with pytest.raises(ValueError, match="'sort' or 'held'"):
            MoE(16, 32, n_experts=4, k=2, dispatch=dispatch)

    @pytest.mark.parametrize("k", [1, 2])
    @pytest.mark.parametrize("cf", [0.5, 1.0, 1.25])
    def test_sort_dispatch_is_the_dense_gshard_forward(self, cf, k):
        """The sort path against the dense GShard reference above: the
        same routing, the same drops and the same renormalised combine
        weights, to float tolerance. The capacity factors cover real
        drops (0.5), the boundary where an expert's run just fits or
        just does not (1.0), and headroom (1.25)."""
        np.random.seed(7)
        m = MoE(16, 32, n_experts=4, k=k,
                capacity_factor=cf).evaluate_mode()
        x = _rand(37, 16)
        ref, _ = gshard_reference(m.parameter_tree(), x, k, cf)
        out = np.asarray(m.forward(x))
        if cf < 1.0:    # tokens all of whose picks dropped are zero rows
            assert (np.abs(np.asarray(ref)).max(axis=-1) == 0).any()
        np.testing.assert_allclose(out, np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("k", [1, 2])
    @pytest.mark.parametrize("cf", [0.5, 0.75, 1.25])
    def test_sort_dispatch_has_the_dense_gshard_gradients(self, cf, k):
        """Gradients through the sort path's gathers against the
        reference's on every parameter leaf, with real drops (cf < 1) and
        with headroom, and with the aux loss in the graph: the layer
        injects it in the backward pass, the reference adds it to the
        loss."""
        np.random.seed(11)
        x = _rand(29, 16)
        m = MoE(16, 32, n_experts=4, k=k, capacity_factor=cf,
                aux_loss_weight=0.1)
        params, buffers = m.parameter_tree(), m.buffer_tree()

        def loss(p):
            y, _ = functional_apply(m, p, buffers, x, training=True)
            return jnp.sum(y * y)

        def ref_loss(p):
            y, aux = gshard_reference(p, x, k, cf, aux_loss_weight=0.1)
            return jnp.sum(y * y) + aux

        grads, ref_grads = jax.grad(loss)(params), jax.grad(ref_loss)(params)
        assert set(grads) == set(ref_grads)
        for name, g in grads.items():
            assert float(jnp.abs(g).max()) > 0, name
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(ref_grads[name]), rtol=2e-4,
                atol=2e-5, err_msg=f"grad mismatch on {name}")

    def test_dispatch_counter_counts_paths(self):
        from bigdl_tpu.telemetry import get_registry, instruments
        fam = instruments(get_registry()).moe_dispatch_total
        before = fam.labels(path="sort").value
        MoE(8, 8, n_experts=2, k=1).evaluate_mode().forward(_rand(4, 8))
        assert fam.labels(path="sort").value == before + 1

    def test_capacity_overflow_at_realistic_token_count(self):
        # 8192 tokens, 8 experts, cf=1.0: the ragged path must (a) never
        # blow up memory with a (T,E,C) mask (8192*8*2048 floats = 512MB
        # would OOM CI), (b) drop overflow tokens to exactly-zero rows,
        # (c) keep every served token's combine weights sane.
        t, d, e = 8192, 32, 8
        # cf=0.25: 8*512 slots for 16384 assignments -> guaranteed overflow
        m = MoE(d, d, n_experts=e, k=2,
                capacity_factor=0.25).evaluate_mode()
        x = _rand(t, d)
        out = np.asarray(m.forward(x))
        assert out.shape == (t, d)
        assert np.isfinite(out).all()
        # tokens whose picks ALL overflowed pass through as zero rows;
        # tokens that got at least one slot must be served
        zero_rows = (np.abs(out).max(axis=-1) < 1e-9).sum()
        assert 0 < zero_rows < t

    def test_aux_loss_reaches_gate_gradient(self):
        m = MoE(8, 8, n_experts=4, k=1, aux_loss_weight=0.1)
        x = _rand(32, 8)
        params, buffers = m.parameter_tree(), m.buffer_tree()

        def loss(p):
            y, _ = functional_apply(m, p, buffers, x, training=True)
            return jnp.sum(y * 0.0)  # downstream ignores y entirely

        g = jax.grad(loss)(params)
        # Only the aux loss can produce a gate gradient here.
        assert float(jnp.abs(g["gate_weight"]).max()) > 0

    def test_inject_loss_identity_forward(self):
        y = _rand(3, 4)
        out = inject_loss(y, jnp.asarray(2.5))
        np.testing.assert_allclose(np.asarray(out), np.asarray(y))
        # aux receives cotangent 1.0 even when downstream multiplies y by 0.
        g = jax.grad(lambda a: jnp.sum(inject_loss(y, a) * 0.0))(
            jnp.asarray(0.0))
        assert float(g) == pytest.approx(1.0)


class TestMoEExpertParallel:
    def test_ep_matches_single_device(self):
        mesh = MeshTopology(expert=4).build()
        m = MoE(16, 32, n_experts=8, k=2).evaluate_mode()
        x = _rand(4, 6, 16)
        ref = m.forward(x)

        params = m.parameter_tree()
        specs = expert_param_specs(m)
        placed = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
                  for k, v in params.items()}
        buffers = m.buffer_tree()

        @jax.jit
        def f(p, x):
            y, _ = functional_apply(m, p, buffers, x, training=False)
            return y

        out = f(placed, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_ep_training_with_distri_optimizer(self):
        from bigdl_tpu.dataset import mnist
        from bigdl_tpu.dataset.base import DataSet
        from bigdl_tpu.dataset.image import (BytesToGreyImg,
                                             GreyImgNormalizer,
                                             GreyImgToBatch)
        from bigdl_tpu.optim import SGD, Trigger
        from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer

        bt.utils.manual_seed(11)
        model = nn.Sequential()
        model.add(nn.Reshape((784,)))
        model.add(nn.Linear(784, 16)).add(nn.ReLU())
        model.add(MoE(16, 32, n_experts=4, k=2))
        model.add(nn.Linear(16, 10)).add(nn.LogSoftMax())

        ds = (DataSet.array(mnist.synthetic(256), distributed=True)
              >> BytesToGreyImg(28, 28) >> GreyImgNormalizer(33.0, 78.0)
              >> GreyImgToBatch(64))
        opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(),
                              topology=MeshTopology(data=2, expert=4))
        opt.set_optim_method(SGD(learningrate=0.05, momentum=0.9))
        opt.set_end_when(Trigger.max_iteration(4))
        trained = opt.optimize()
        assert trained is model
