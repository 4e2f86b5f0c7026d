"""ResNet (reference ``models/resnet/ResNet.scala:58``): CIFAR-10 basic-block
variants (depth = 6n+2) and ImageNet bottleneck variants (50/101/152).

Built from the container zoo exactly like the reference (Sequential +
ConcatTable(shortcut, main) + CAddTable + ReLU); kaiming/MSR init on convs
(reference ``MSRinit``), BN gamma=1 beta=0, channels-last layout. Shortcut
type B (1x1 conv projection on dimension change) is the default, as in the
reference's ImageNet config.
"""

from __future__ import annotations

from bigdl_tpu import nn

_IMAGENET_CFG = {
    18: ([2, 2, 2, 2], "basic"),
    34: ([3, 4, 6, 3], "basic"),
    50: ([3, 4, 6, 3], "bottleneck"),
    101: ([3, 4, 23, 3], "bottleneck"),
    152: ([3, 8, 36, 3], "bottleneck"),
}


def _conv(n_in, n_out, k, stride=1, pad=0):
    return nn.SpatialConvolution(n_in, n_out, k, k, stride, stride, pad, pad,
                                 with_bias=False, init_method="kaiming")


def _add_conv_bn(seq, n_in, n_out, k, stride=1, pad=0):
    return (seq.add(_conv(n_in, n_out, k, stride, pad))
            .add(nn.SpatialBatchNormalization(n_out)))


def _shortcut(n_in, n_out, stride, shortcut_type="B"):
    if n_in != n_out or stride != 1:
        if shortcut_type == "A":
            # identity + zero-pad channels (dim 3 = C in HWC), avg-pool spatial
            return (nn.Sequential()
                    .add(nn.SpatialAveragePooling(1, 1, stride, stride))
                    .add(nn.Padding(3, n_out - n_in, 3)))
        return _add_conv_bn(nn.Sequential(), n_in, n_out, 1, stride)
    return nn.Identity()


def _basic_block(n_in, n_out, stride, shortcut_type="B"):
    main = _add_conv_bn(nn.Sequential(), n_in, n_out, 3, stride, 1)
    main.add(nn.ReLU())
    _add_conv_bn(main, n_out, n_out, 3, 1, 1)
    return (nn.Sequential()
            .add(nn.ConcatTable().add(main).add(_shortcut(n_in, n_out, stride,
                                                          shortcut_type)))
            .add(nn.CAddTable())
            .add(nn.ReLU()))


def _bottleneck(n_in, n_mid, stride, shortcut_type="B"):
    n_out = n_mid * 4
    main = _add_conv_bn(nn.Sequential(), n_in, n_mid, 1)
    main.add(nn.ReLU())
    _add_conv_bn(main, n_mid, n_mid, 3, stride, 1)
    main.add(nn.ReLU())
    _add_conv_bn(main, n_mid, n_out, 1)
    return (nn.Sequential()
            .add(nn.ConcatTable().add(main).add(_shortcut(n_in, n_out, stride,
                                                          shortcut_type)))
            .add(nn.CAddTable())
            .add(nn.ReLU()))


def build(class_num: int = 1000, depth: int = 50,
          shortcut_type: str = "B") -> nn.Sequential:
    """ImageNet ResNet; input (N, 224, 224, 3)."""
    assert depth in _IMAGENET_CFG, f"unsupported depth {depth}"
    layers, block_kind = _IMAGENET_CFG[depth]
    model = (nn.Sequential()
             .add(nn.stem_conv7(3, 64, with_bias=False,
                                init_method="kaiming"))
             .add(nn.SpatialBatchNormalization(64))
             .add(nn.ReLU())
             .add(nn.SpatialMaxPooling(3, 3, 2, 2, 1, 1)))
    widths = [64, 128, 256, 512]
    n_in = 64
    for stage, (w, reps) in enumerate(zip(widths, layers)):
        for i in range(reps):
            stride = 2 if (stage > 0 and i == 0) else 1
            if block_kind == "bottleneck":
                model.add(_bottleneck(n_in, w, stride, shortcut_type))
                n_in = w * 4
            else:
                model.add(_basic_block(n_in, w, stride, shortcut_type))
                n_in = w
    model.add(nn.SpatialAveragePooling(7, 7, 1, 1))
    model.add(nn.Reshape((n_in,), batch_mode=True))
    model.add(nn.Linear(n_in, class_num))
    model.add(nn.LogSoftMax())
    return model


def build_cifar(class_num: int = 10, depth: int = 20,
                shortcut_type: str = "A") -> nn.Sequential:
    """CIFAR ResNet (depth = 6n+2; reference CIFAR config uses shortcut A).
    Input (N, 32, 32, 3)."""
    assert (depth - 2) % 6 == 0, "CIFAR ResNet depth must be 6n+2"
    n = (depth - 2) // 6
    model = _add_conv_bn(nn.Sequential(), 3, 16, 3, 1, 1)
    model.add(nn.ReLU())
    n_in = 16
    for stage, w in enumerate([16, 32, 64]):
        for i in range(n):
            stride = 2 if (stage > 0 and i == 0) else 1
            model.add(_basic_block(n_in, w, stride, shortcut_type))
            n_in = w
    model.add(nn.SpatialAveragePooling(8, 8, 1, 1))
    model.add(nn.Reshape((64,), batch_mode=True))
    model.add(nn.Linear(64, class_num))
    model.add(nn.LogSoftMax())
    return model
