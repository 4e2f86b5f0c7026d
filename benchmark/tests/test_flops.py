"""``flops.py`` against counts made by hand."""

import pytest

from benchmark import flops, harness


def test_one_qwen2_layer_by_hand():
    _, cfg = harness.load_cell("qwen2.5-0.5b-train-s2048")
    s = 2048
    # q: 896x896, k and v: 896x128 each, out: 896x896 -> 1,835,008 MACs a
    # token; MLP: 3 x 896x4864 = 13,074,432 MACs a token; attention: 14
    # heads x 64 x (QK^T + PV) over the causal half = 14*64*2*s/2 MACs
    macs_token = 896 * 896 * 2 + 896 * 128 * 2 + 3 * 896 * 4864 \
        + 14 * 64 * 2 * s // 2
    assert macs_token == 16_744_448
    assert flops.decoder_layer_forward_flops(cfg, s) == 2 * macs_token * s
    head = 2 * 896 * 151936
    per_token = (24 * 2 * macs_token + head) * 3
    assert flops.lm_train_flops_per_record(cfg, s) == per_token * s
    assert per_token == pytest.approx(3.228e9, rel=1e-3)


def test_one_bottleneck_block_and_the_whole_resnet_by_hand():
    # conv2_1 at 56x56: 1x1 64->64, 3x3 64->64, 1x1 64->256, projection 1x1
    # 64->256: 56*56 * (4096 + 36864 + 16384 + 16384) MACs
    macs = 56 * 56 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    assert macs == 231_211_008
    assert flops.bottleneck_forward_flops(56, 64, 64, 1) == 2 * macs
    # conv3_1 takes 56x56x256 down to 28x28x512, stride on the 3x3
    macs = 56 * 56 * 256 * 128 + 28 * 28 * (9 * 128 * 128 + 128 * 512
                                            + 256 * 512)
    assert flops.bottleneck_forward_flops(56, 256, 128, 2) == 2 * macs
    _, cfg = harness.load_cell("resnet50-train-dp4")
    # the published ~4.1 GMACs of ResNet-50 at 224x224
    assert flops.resnet_forward_flops(cfg) / 2 == pytest.approx(4.09e9,
                                                                rel=5e-3)
    assert flops.resnet_train_flops_per_record(cfg) == \
        3 * flops.resnet_forward_flops(cfg)


def test_flash_forward_cost_by_hand():
    f, b = flops.flash_forward_cost(2, 14, 2048, 64)
    assert f == 2 * 2 * 28 * 64 * 2048 * 2048 / 2
    assert b == 4 * 28 * 2048 * 64 * 2 + 4 * 28 * 2048
    assert f / b > 240      # FLOPs a byte: compute-bound on a v5e (197e12/819e9)
