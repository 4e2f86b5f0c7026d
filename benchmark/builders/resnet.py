"""ResNet configuration -> the program's model, its training data, and its
parameters in the plain reference's layout."""

from __future__ import annotations

import numpy as np

from benchmark import flops, traffic
from benchmark.reference import resnet50 as reference


def build(cfg, seed):
    from bigdl_tpu.models import resnet
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(seed)
    np.random.seed(seed % (2 ** 32))
    return resnet.build(cfg["num_classes"], depth=cfg["depth"])


def criterion(cfg):
    from bigdl_tpu import nn
    return nn.ClassNLLCriterion()


def train_samples(cfg, cell, seed):
    """``records_per_epoch`` Samples over ``data.distinct_images`` seeded
    noise images, reused by reference so set-up stays short."""
    from bigdl_tpu.dataset.base import Sample
    d = cfg["data"]
    x, y = traffic.noise_images(seed, d["distinct_images"], cfg["image_size"],
                                cfg["image_channels"], d["label_classes"],
                                dtype=cell.get("cast_dtype") or "float32")
    n = len(x)      # Sample keeps a view of x: no copy until the cache stacks
    return [Sample(x[i % n], y[i % n])
            for i in range(cell["records_per_epoch"])]


def reference_batch(cfg, cell, seed):
    d = cfg["data"]
    x, y = traffic.noise_images(seed + 1, cell["reference"]["batch"],
                                cfg["image_size"], cfg["image_channels"],
                                d["label_classes"])
    return x, y


def reference_params(model, cfg):
    """(the model's parameters in the reference's layout, strides a block);
    the tree's keys are the Sequential's indices: 0 conv, 1 bn, 2 relu, 3
    max-pool, then a block each, then avg-pool, reshape, Linear."""
    tree = model.parameter_tree()
    blocks, strides, idx = [], [], 4
    for stage, reps in enumerate(cfg["stage_blocks"]):
        for i in range(reps):
            main = tree[str(idx)]["0"]["0"]
            short = tree[str(idx)]["0"].get("1")
            blocks.append({
                "conv": [main[k]["weight"] for k in ("0", "3", "6")],
                "bn": [(main[k]["weight"], main[k]["bias"])
                       for k in ("1", "4", "7")],
                "down": (short["0"]["weight"], short["1"]["weight"],
                         short["1"]["bias"]) if short else None})
            strides.append(2 if (stage > 0 and i == 0) else 1)
            idx += 1
    fc = tree[str(idx + 2)]
    return {"stem": (tree["0"]["weight"], tree["1"]["weight"],
                     tree["1"]["bias"]),
            "blocks": blocks, "fc": (fc["weight"], fc["bias"])}, strides


def reference_loss_and_grad_norm(model, cfg, data, labels):
    import jax
    import jax.numpy as jnp
    p, strides = reference_params(model, cfg)
    fn = jax.jit(lambda p, x, y: reference.loss_and_grad_norm(
        p, strides, x, y, cfg["bn_eps"]))
    with jax.default_matmul_precision("highest"):
        loss, gn = fn(p, jnp.asarray(data, jnp.float32),
                      jnp.asarray(labels, jnp.int32) - 1)
    return float(loss), float(gn)


def train_flops_per_record(cfg, cell):
    return flops.resnet_train_flops_per_record(cfg)
