"""SpaceToDepthConv7: exact parity with the plain 7x7/s2/p3 stem conv.

The packed formulation (MLPerf ResNet space-to-depth trick, adopted for the
ResNet/Inception stems in round 3 — PERF.md) must be numerically identical:
same parameter tree ("weight" (7,7,C,O) [+ "bias"]), same function. Any
divergence is a packing/padding bug, not tolerance."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.nn.module import functional_apply


def _plain_from(s2d, with_bias):
    conv = nn.SpatialConvolution(s2d.n_input_plane, s2d.n_output_plane,
                                 7, 7, 2, 2, 3, 3, with_bias=with_bias)
    conv.weight = s2d.weight
    if with_bias:
        conv.bias = s2d.bias
    return conv


@pytest.mark.parametrize("with_bias", [False, True])
@pytest.mark.parametrize("hw", [(224, 224), (56, 84), (31, 45), (225, 227)])
def test_forward_parity(with_bias, hw):
    rng = np.random.default_rng(0)
    h, w = hw
    s2d = nn.SpaceToDepthConv7(3, 16, with_bias=with_bias,
                               init_method="kaiming")
    plain = _plain_from(s2d, with_bias)
    x = jnp.asarray(rng.normal(0, 1, (2, h, w, 3)), jnp.float32)
    np.testing.assert_allclose(np.asarray(s2d.forward(x)),
                               np.asarray(plain.forward(x)),
                               rtol=1e-5, atol=1e-5)


def test_grad_parity():
    rng = np.random.default_rng(1)
    s2d = nn.SpaceToDepthConv7(3, 8, with_bias=True)
    plain = _plain_from(s2d, True)
    x = jnp.asarray(rng.normal(0, 1, (2, 32, 32, 3)), jnp.float32)
    cvec = jnp.asarray(rng.normal(0, 1, (2, 16, 16, 8)), jnp.float32)

    def loss(mod, p):
        out, _ = functional_apply(mod, p, mod.buffer_tree(), x,
                                  training=True)
        return jnp.sum(out * cvec)

    g_s2d = jax.grad(lambda p: loss(s2d, p))(s2d.parameter_tree())
    g_plain = jax.grad(lambda p: loss(plain, p))(plain.parameter_tree())
    # identical parameter-tree structure (checkpoint compatibility)
    assert (jax.tree_util.tree_structure(g_s2d)
            == jax.tree_util.tree_structure(g_plain))
    for a, b in zip(jax.tree_util.tree_leaves(g_s2d),
                    jax.tree_util.tree_leaves(g_plain)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_unbatched_and_repr():
    rng = np.random.default_rng(2)
    s2d = nn.SpaceToDepthConv7(3, 4, with_bias=False)
    x = jnp.asarray(rng.normal(0, 1, (16, 16, 3)), jnp.float32)
    assert s2d.forward(x).shape == (8, 8, 4)
    assert "space-to-depth" in repr(s2d)


def test_resnet_stem_uses_s2d_and_matches_plain():
    # resnet.build has the packed stem; the same layers behind the plain
    # 7x7 conv compute the same function on the same weights.
    from bigdl_tpu.models import resnet
    rng = np.random.default_rng(3)
    m_s2d = resnet.build(class_num=10, depth=18)
    assert isinstance(m_s2d._modules["0"], nn.SpaceToDepthConv7)
    m_plain = nn.Sequential().add(nn.SpatialConvolution(
        3, 64, 7, 7, 2, 2, 3, 3, with_bias=False, init_method="kaiming"))
    for i in range(1, len(m_s2d)):
        m_plain.add(m_s2d[i])

    params = m_s2d.parameter_tree()
    x = jnp.asarray(rng.normal(0, 1, (2, 224, 224, 3)), jnp.float32)
    out_a, _ = functional_apply(m_s2d, params, m_s2d.buffer_tree(), x,
                                training=False)
    out_b, _ = functional_apply(m_plain, params, m_plain.buffer_tree(), x,
                                training=False)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                               rtol=1e-4, atol=1e-4)
