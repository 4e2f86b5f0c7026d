"""VGG for CIFAR-10 (reference ``models/vgg/VggForCifar10.scala``) and
configurable VGG-16/19 for ImageNet (the reference's perf-harness models,
``models/utils/LocalOptimizerPerf.scala``). Channels-last input.
"""

from __future__ import annotations

from bigdl_tpu import nn

_IMAGENET_CFG = {
    16: [64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
         512, 512, 512, "M", 512, 512, 512, "M"],
    19: [64, 64, "M", 128, 128, "M", 256, 256, 256, 256, "M",
         512, 512, 512, 512, "M", 512, 512, 512, 512, "M"],
}


def _conv_bn_relu(model, n_in, n_out):
    (model.add(nn.SpatialConvolution(n_in, n_out, 3, 3, 1, 1, 1, 1,
                                     init_method="kaiming"))
          .add(nn.SpatialBatchNormalization(n_out))
          .add(nn.ReLU(True)))
    return n_out


def build(class_num: int = 10) -> nn.Sequential:
    """VggForCifar10: input (N, 32, 32, 3)."""
    model = nn.Sequential()
    n_in = 3
    for block in ([64, 64], [128, 128], [256, 256, 256],
                  [512, 512, 512], [512, 512, 512]):
        for w in block:
            n_in = _conv_bn_relu(model, n_in, w)
        model.add(nn.SpatialMaxPooling(2, 2, 2, 2).ceil())
    (model.add(nn.Reshape((512,), batch_mode=True))
          .add(nn.Linear(512, 512))
          .add(nn.BatchNormalization(512))
          .add(nn.ReLU(True))
          .add(nn.Dropout(0.5))
          .add(nn.Linear(512, class_num))
          .add(nn.LogSoftMax()))
    return model


def build_imagenet(class_num: int = 1000, depth: int = 16) -> nn.Sequential:
    """VGG-16/19: input (N, 224, 224, 3)."""
    model = nn.Sequential()
    n_in = 3
    for v in _IMAGENET_CFG[depth]:
        if v == "M":
            model.add(nn.SpatialMaxPooling(2, 2, 2, 2))
        else:
            n_in = _conv_bn_relu(model, n_in, v)
    (model.add(nn.Reshape((512 * 7 * 7,), batch_mode=True))
          .add(nn.Linear(512 * 7 * 7, 4096))
          .add(nn.ReLU(True))
          .add(nn.Dropout(0.5))
          .add(nn.Linear(4096, 4096))
          .add(nn.ReLU(True))
          .add(nn.Dropout(0.5))
          .add(nn.Linear(4096, class_num))
          .add(nn.LogSoftMax()))
    return model
