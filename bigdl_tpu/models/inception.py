"""Inception v1 / GoogLeNet (reference ``models/inception/Inception_v1.scala``)
and Inception v2 / BN-Inception (``models/inception/Inception_v2.scala``),
built as Concat-of-Sequential branches like the reference; channels-last.
"""

from __future__ import annotations

from bigdl_tpu import nn


def _conv(n_in, n_out, kw, kh, sw=1, sh=1, pw=0, ph=0, name=""):
    return (nn.Sequential()
            .add(nn.SpatialConvolution(n_in, n_out, kw, kh, sw, sh, pw, ph,
                                       init_method="xavier").set_name(name + "conv"))
            .add(nn.ReLU(True)))


def inception_module(n_in, c1x1, c3x3r, c3x3, c5x5r, c5x5, pool_proj,
                     name="inception"):
    """One inception block: 4 parallel branches concatenated on channels
    (reference ``Inception_v1.scala`` inception() builder — Concat on dim 1
    of NCHW, i.e. the channel axis)."""
    concat = nn.Concat(1).set_name(name)
    concat.add(_conv(n_in, c1x1, 1, 1, name=f"{name}/1x1/"))
    concat.add(nn.Sequential()
               .add(_conv(n_in, c3x3r, 1, 1, name=f"{name}/3x3r/"))
               .add(_conv(c3x3r, c3x3, 3, 3, 1, 1, 1, 1, name=f"{name}/3x3/")))
    concat.add(nn.Sequential()
               .add(_conv(n_in, c5x5r, 1, 1, name=f"{name}/5x5r/"))
               .add(_conv(c5x5r, c5x5, 5, 5, 1, 1, 2, 2, name=f"{name}/5x5/")))
    concat.add(nn.Sequential()
               .add(nn.SpatialMaxPooling(3, 3, 1, 1, 1, 1))
               .add(_conv(n_in, pool_proj, 1, 1, name=f"{name}/pool_proj/")))
    return concat


def build(class_num: int = 1000) -> nn.Sequential:
    """Inception v1 main tower (no aux classifiers, like the reference's
    ``Inception_v1_NoAuxClassifier``); input (N, 224, 224, 3)."""
    model = (nn.Sequential()
             .add(nn.stem_conv7(3, 64, init_method="xavier",
                                name="conv1/7x7_s2"))
             .add(nn.ReLU(True))
             .add(nn.SpatialMaxPooling(3, 3, 2, 2).ceil())
             .add(nn.SpatialCrossMapLRN(5, 0.0001, 0.75))
             .add(_conv(64, 64, 1, 1, name="conv2/3x3_reduce/"))
             .add(_conv(64, 192, 3, 3, 1, 1, 1, 1, name="conv2/3x3/"))
             .add(nn.SpatialCrossMapLRN(5, 0.0001, 0.75))
             .add(nn.SpatialMaxPooling(3, 3, 2, 2).ceil())
             .add(inception_module(192, 64, 96, 128, 16, 32, 32, "inception_3a"))
             .add(inception_module(256, 128, 128, 192, 32, 96, 64, "inception_3b"))
             .add(nn.SpatialMaxPooling(3, 3, 2, 2).ceil())
             .add(inception_module(480, 192, 96, 208, 16, 48, 64, "inception_4a"))
             .add(inception_module(512, 160, 112, 224, 24, 64, 64, "inception_4b"))
             .add(inception_module(512, 128, 128, 256, 24, 64, 64, "inception_4c"))
             .add(inception_module(512, 112, 144, 288, 32, 64, 64, "inception_4d"))
             .add(inception_module(528, 256, 160, 320, 32, 128, 128, "inception_4e"))
             .add(nn.SpatialMaxPooling(3, 3, 2, 2).ceil())
             .add(inception_module(832, 256, 160, 320, 32, 128, 128, "inception_5a"))
             .add(inception_module(832, 384, 192, 384, 48, 128, 128, "inception_5b"))
             .add(nn.SpatialAveragePooling(7, 7, 1, 1))
             .add(nn.Dropout(0.4))
             .add(nn.Reshape((1024,), batch_mode=True))
             .add(nn.Linear(1024, class_num).set_name("loss3/classifier"))
             .add(nn.LogSoftMax()))
    return model


def _conv_bn(n_in, n_out, kw, kh, sw=1, sh=1, pw=0, ph=0, name=""):
    """conv -> BN(eps=1e-3) -> ReLU triple used throughout Inception v2
    (reference ``Inception_v2.scala`` Inception_Layer_v2)."""
    if (kw, kh, sw, sh, pw, ph) == (7, 7, 2, 2, 3, 3):
        # ImageNet stem: space-to-depth form (PERF.md round 3)
        return (nn.Sequential()
                .add(nn.stem_conv7(n_in, n_out, init_method="xavier",
                                   name=name))
                .add(nn.SpatialBatchNormalization(n_out, 1e-3)
                     .set_name(name + "/bn"))
                .add(nn.ReLU(True)))
    return (nn.Sequential()
            .add(nn.SpatialConvolution(n_in, n_out, kw, kh, sw, sh, pw, ph,
                                       init_method="xavier").set_name(name))
            .add(nn.SpatialBatchNormalization(n_out, 1e-3)
                 .set_name(name + "/bn"))
            .add(nn.ReLU(True)))


def inception_module_v2(n_in, c1x1, c3x3r, c3x3, cd3x3r, cd3x3, pool_mode,
                        pool_proj, name="inception"):
    """One BN-Inception block: 1x1 / 3x3 / double-3x3 / pool branches.

    ``c1x1 == 0`` drops the 1x1 branch and switches the 3x3 / double-3x3
    tails to stride 2 (the grid-reduction blocks 3c/4e); ``pool_mode`` is
    "avg" or "max", with ``pool_proj == 0`` meaning a stride-2 max pool and
    no projection (reference ``Inception_v2.scala`` Inception_Layer_v2)."""
    reduction = c1x1 == 0
    stride = 2 if reduction else 1
    concat = nn.Concat(1).set_name(name)
    if not reduction:
        concat.add(_conv_bn(n_in, c1x1, 1, 1, name=f"{name}/1x1"))
    concat.add(nn.Sequential()
               .add(_conv_bn(n_in, c3x3r, 1, 1, name=f"{name}/3x3_reduce"))
               .add(_conv_bn(c3x3r, c3x3, 3, 3, stride, stride, 1, 1,
                             name=f"{name}/3x3")))
    concat.add(nn.Sequential()
               .add(_conv_bn(n_in, cd3x3r, 1, 1,
                             name=f"{name}/double3x3_reduce"))
               .add(_conv_bn(cd3x3r, cd3x3, 3, 3, 1, 1, 1, 1,
                             name=f"{name}/double3x3a"))
               .add(_conv_bn(cd3x3, cd3x3, 3, 3, stride, stride, 1, 1,
                             name=f"{name}/double3x3b")))
    pool = nn.Sequential()
    if pool_mode == "avg":
        pool.add(nn.SpatialAveragePooling(3, 3, 1, 1, 1, 1).ceil())
    elif pool_proj != 0:
        pool.add(nn.SpatialMaxPooling(3, 3, 1, 1, 1, 1).ceil())
    else:
        pool.add(nn.SpatialMaxPooling(3, 3, 2, 2).ceil())
    if pool_proj != 0:
        pool.add(_conv_bn(n_in, pool_proj, 1, 1, name=f"{name}/pool_proj"))
    concat.add(pool)
    return concat


def build_v2(class_num: int = 1000) -> nn.Sequential:
    """Inception v2 / BN-Inception main tower (no aux classifiers, like the
    reference's ``Inception_v2_NoAuxClassifier``); input (N, 224, 224, 3)."""
    model = (nn.Sequential()
             .add(_conv_bn(3, 64, 7, 7, 2, 2, 3, 3, name="conv1/7x7_s2"))
             .add(nn.SpatialMaxPooling(3, 3, 2, 2).ceil())
             .add(_conv_bn(64, 64, 1, 1, name="conv2/3x3_reduce"))
             .add(_conv_bn(64, 192, 3, 3, 1, 1, 1, 1, name="conv2/3x3"))
             .add(nn.SpatialMaxPooling(3, 3, 2, 2).ceil())
             .add(inception_module_v2(192, 64, 64, 64, 64, 96, "avg", 32,
                                      "inception_3a"))
             .add(inception_module_v2(256, 64, 64, 96, 64, 96, "avg", 64,
                                      "inception_3b"))
             .add(inception_module_v2(320, 0, 128, 160, 64, 96, "max", 0,
                                      "inception_3c"))
             .add(inception_module_v2(576, 224, 64, 96, 96, 128, "avg", 128,
                                      "inception_4a"))
             .add(inception_module_v2(576, 192, 96, 128, 96, 128, "avg", 128,
                                      "inception_4b"))
             .add(inception_module_v2(576, 160, 128, 160, 128, 160, "avg", 96,
                                      "inception_4c"))
             .add(inception_module_v2(576, 96, 128, 192, 160, 192, "avg", 96,
                                      "inception_4d"))
             .add(inception_module_v2(576, 0, 128, 192, 192, 256, "max", 0,
                                      "inception_4e"))
             .add(inception_module_v2(1024, 352, 192, 320, 160, 224, "avg",
                                      128, "inception_5a"))
             .add(inception_module_v2(1024, 352, 192, 320, 192, 224, "max",
                                      128, "inception_5b"))
             .add(nn.SpatialAveragePooling(7, 7, 1, 1).ceil())
             .add(nn.Reshape((1024,), batch_mode=True))
             .add(nn.Linear(1024, class_num).set_name("loss3/classifier"))
             .add(nn.LogSoftMax()))
    return model
