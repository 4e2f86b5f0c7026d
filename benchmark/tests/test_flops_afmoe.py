"""``flops_afmoe.py`` and the new readers' costs against counts made by
hand, at the published widths of the Trinity cell."""

import pytest

from benchmark import flops, flops_afmoe as fa, harness
from benchmark.layer_metrics import (flash_band_fwd_roofline,
                                     flash_band_share,
                                     moe_gated_experts_roofline)

CELL = "trinity-mini-train-s8192"
S = 8192


@pytest.fixture(scope="module")
def cfg():
    return harness.load_cell(CELL)[1]


def test_the_bands_pairs_by_hand():
    # a full layer: the causal half with its diagonal
    assert fa.band_pairs(S) == S * (S + 1) // 2 == 33_558_528
    # a window of 2,048: the first 2,048 queries see 1..2,048 keys, the
    # other 6,144 see 2,048 each
    assert fa.band_pairs(S, 2048) == 2048 * 2049 // 2 + 6144 * 2048 \
        == 14_681_088
    assert fa.band_pairs(S, 2048) / fa.band_pairs(S) == pytest.approx(
        0.4375, rel=1e-3)
    assert fa.band_pairs(S, S) == fa.band_pairs(S, 3 * S) == fa.band_pairs(S)
    assert fa.band_pairs(5, 2) == 1 + 2 + 2 + 2 + 2
    assert fa.band_pairs(4, 1) == 4


def test_the_banded_forward_call_by_hand():
    f, b = fa.flash_band_forward_cost(1, 32, S, 128, 2048)
    assert f == 2 * 2 * 32 * 128 * 14_681_088
    # q, k, v in, o out in bf16, the float32 LSE row
    assert b == 4 * 32 * S * 128 * 2 + 4 * 32 * S
    # without a band it is the full call's count, diagonal included
    full, same = fa.flash_band_forward_cost(1, 32, S, 128, None)
    assert same == b == flops.flash_forward_cost(1, 32, S, 128)[1]
    assert full == pytest.approx(flops.flash_forward_cost(1, 32, S, 128)[0],
                                 rel=2e-4)
    # compute-bound on the v5e: 1.22 ms of FLOPs against 0.33 ms of bytes
    assert f / 197e12 == pytest.approx(1.221e-3, rel=1e-3)
    assert b / 819e9 == pytest.approx(0.329e-3, rel=1e-2)


def test_one_attention_layer_by_hand(cfg):
    # q 2048 x 4096, k and v 2048 x 512 each, the gate 2048 x 4096, out
    # 4096 x 2048: 27,262,976 MACs a token
    proj = 2048 * (4096 + 512 + 512 + 4096) + 4096 * 2048
    assert proj == 27_262_976
    for kind, pairs in (("sliding_attention", 14_681_088),
                        ("full_attention", 33_558_528)):
        assert fa.attention_layer_forward_flops(cfg, S, kind) \
            == 2 * proj * S + 2 * 2 * 32 * 128 * pairs


def test_the_feed_forwards_by_hand(cfg):
    # 8,192 tokens x 8 picks x 16 held / 128 routed = 8,192 picks land here
    assert fa.expected_picks(cfg, S) == 8192
    assert fa.dense_layer_forward_flops(cfg, S) == 2 * 3 * 2048 * 6144 * S
    router, one = 2048 * 128 * S, 3 * 2048 * 1024 * S
    assert fa.moe_layer_forward_flops(cfg, S) == 2 * (router + one + one)
    f, b = fa.moe_gated_experts_cost(cfg, S)
    assert f == 3 * 2 * 3 * 2048 * 1024 * 8192
    # 16 experts x 6.29M weights read twice in bf16 and their gradient
    # written in float32, five rows of 2,048 a pick
    assert b == 16 * 3 * 2048 * 1024 * 8 + 5 * 8192 * 2048 * 2
    assert moe_gated_experts_roofline.cost(cfg, S) == (4 * f, 4 * b)
    # bound by FLOPs: 1.57 ms against 1.19 ms a layer
    assert f / 197e12 > b / 819e9


def test_the_whole_step_by_hand(cfg):
    fwd = 4 * fa.attention_layer_forward_flops(cfg, S, "sliding_attention") \
        + fa.attention_layer_forward_flops(cfg, S, "full_attention") \
        + fa.dense_layer_forward_flops(cfg, S) \
        + 4 * fa.moe_layer_forward_flops(cfg, S) + 2 * 2048 * 25024 * S
    assert fa.train_flops_per_record(cfg, S) == 3 * fwd
    assert fa.train_flops_per_record(cfg, S) == pytest.approx(18.14e12,
                                                              rel=1e-3)


class _Op:
    is_mosaic = True

    def __init__(self, name, t0, dur):
        self.name, self.t0, self.t1, self.dur = name, t0, t0 + dur, dur


class _Trace:
    def __init__(self, ops):
        self.devices = [type("Dev", (), {"ops": ops, "modules": [],
                                         "name": "/device:TPU:0"})()]


def test_the_band_readers_read_the_calls_by_name(cfg):
    """Banded calls are told from full ones of the same operand shape by
    their names; a program without them (the parent) reads nothing."""
    cell = harness.load_cell(CELL)[0]
    least = 2 * 2 * 32 * 128 * 14_681_088 / 197e12
    ops = [_Op("flash_band_fwd.3", 0.0, 2 * least),
           _Op("flash_band_fwd", 0.1, 2 * least),
           _Op("flash_band_bwd_dq.1", 0.2, 0.003),
           _Op("flash_fwd.2", 0.3, 0.005), _Op("fusion.7", 0.4, 0.1)]
    ctx = {"trace": _Trace(ops), "lo": 0.0, "hi": 1.0, "config": cfg,
           "cell": cell,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert [o.name for o in flash_band_share.band_ops(ctx)] == [
        "flash_band_fwd.3", "flash_band_fwd", "flash_band_bwd_dq.1"]
    assert flash_band_fwd_roofline.read(ctx) == pytest.approx(50.0)
    parent = dict(ctx, trace=_Trace(ops[3:]))
    assert flash_band_share.read(parent) is None
    assert flash_band_fwd_roofline.read(parent) is None
    assert moe_gated_experts_roofline.read(dict(ctx, hlo="")) is None
    nemotron = harness.load_cell("nemotron-3-nano-30b-a3b-train-s8192")[1]
    assert flash_band_fwd_roofline.read(dict(ctx, config=nemotron)) is None
