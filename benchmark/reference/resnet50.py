"""Plain reference of the bottleneck ResNet (He et al., arXiv:1512.03385,
Table 1; torchvision's ``resnet50`` layout, stride on the 3x3): float32,
``lax.conv_general_dilated`` and ``jax.numpy`` only, batch-statistics
BatchNorm (training mode, biased variance), no fused kernels. Callers wrap
it in ``jax.default_matmul_precision("highest")``.

Parameters: {"stem": (w, gamma, beta), "blocks": [{"conv": [w1, w2, w3],
"bn": [(g, b)] * 3, "down": (w, g, b) or None}], "fc": (w (classes,
features), b)}, with ``strides`` (1 or 2 a block) beside them; conv weights
are HWIO, images NHWC. Departure
from the paper: none in the mathematics; the data is noise (see the
configuration's ``assumed``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def conv(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def batch_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean((x - mean) ** 2, axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + eps) * gamma + beta


def log_probs(p, strides, images, eps):
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    w, g, b = p["stem"]
    x = jax.nn.relu(batch_norm(conv(images.astype(jnp.float32), w, 2, 3),
                               g, b, eps))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)])
    for blk, s in zip(p["blocks"], strides):
        (w1, w2, w3), bns = blk["conv"], blk["bn"]
        y = jax.nn.relu(batch_norm(conv(x, w1, 1, 0), *bns[0], eps))
        y = jax.nn.relu(batch_norm(conv(y, w2, s, 1), *bns[1], eps))
        y = batch_norm(conv(y, w3, 1, 0), *bns[2], eps)
        if blk["down"] is not None:
            wd, gd, bd = blk["down"]
            x = batch_norm(conv(x, wd, s, 0), gd, bd, eps)
        x = jax.nn.relu(x + y)
    x = jnp.mean(x, axis=(1, 2))
    wf, bf = p["fc"]
    return jax.nn.log_softmax(x @ wf.T + bf, -1)


def loss(p, strides, images, labels0, eps):
    lp = log_probs(p, strides, images, eps)
    return -jnp.mean(jnp.take_along_axis(lp, labels0[:, None], -1))


def loss_and_grad_norm(p, strides, images, labels0, eps):
    val, g = jax.value_and_grad(loss)(p, strides, images, labels0, eps)
    sq = sum(jnp.sum(x.astype(jnp.float32) ** 2)
             for x in jax.tree_util.tree_leaves(g))
    return val, jnp.sqrt(sq)
