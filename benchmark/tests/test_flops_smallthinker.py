"""``flops_smallthinker.py`` and the new readers' costs against counts made
by hand, at the published widths of the SmallThinker cell."""

import pytest

from benchmark import flops, flops_smallthinker as fs, harness
from benchmark.layer_metrics import (flash16k_fwd_roofline,
                                     flash_band16k_fwd_roofline,
                                     moe_reglu_experts_roofline,
                                     moe_route_ahead_share)

CELL = "smallthinker-21b-a3b-train-s16384"
S = 16384


@pytest.fixture(scope="module")
def cfg():
    return harness.load_cell(CELL)[1]


def test_the_bands_pairs_by_hand():
    # the full layer: the causal half with its diagonal
    assert fs.band_pairs(S) == S * (S + 1) // 2 == 134_225_920
    # a window of 4,096: the first 4,096 queries see 1..4,096 keys, the
    # other 12,288 see 4,096 each
    assert fs.band_pairs(S, 4096) == 4096 * 4097 // 2 + 12288 * 4096 \
        == 58_722_304
    # 44% of a full layer's pairs at 16,384; it would be 75% at 8,192
    assert fs.band_pairs(S, 4096) / fs.band_pairs(S) == pytest.approx(
        0.4375, rel=1e-3)
    assert fs.band_pairs(8192, 4096) / fs.band_pairs(8192) \
        == pytest.approx(0.75, rel=1e-3)
    assert fs.band_pairs(S, S) == fs.band_pairs(S, 3 * S) == fs.band_pairs(S)
    assert fs.band_pairs(5, 2) == 1 + 2 + 2 + 2 + 2


def test_the_forward_calls_by_hand():
    f, b = fs.flash_forward_cost(1, 28, S, 128, 4096)
    assert f == 2 * 2 * 28 * 128 * 58_722_304
    # q, k, v in, o out in bf16, the float32 LSE row
    assert b == 4 * 28 * S * 128 * 2 + 4 * 28 * S
    full, same = fs.flash_forward_cost(1, 28, S, 128)
    assert same == b == flops.flash_forward_cost(1, 28, S, 128)[1]
    assert full == 2 * 2 * 28 * 128 * 134_225_920
    assert full == pytest.approx(flops.flash_forward_cost(1, 28, S, 128)[0],
                                 rel=2e-4)
    # compute-bound on the v5e: 9.77 and 4.27 ms of FLOPs against 0.58 ms
    # of bytes a call
    assert full / 197e12 == pytest.approx(9.768e-3, rel=1e-3)
    assert f / 197e12 == pytest.approx(4.273e-3, rel=1e-3)
    assert b / 819e9 == pytest.approx(0.576e-3, rel=1e-2)


def test_one_attention_layer_by_hand(cfg):
    # q 2560 x 3584, k and v 2560 x 512 each, out 3584 x 2560:
    # 20,971,520 MACs a token
    proj = 2560 * (3584 + 512 + 512) + 3584 * 2560
    assert proj == 20_971_520
    assert [fs.layer_window(cfg, i) for i in range(4)] \
        == [None, 4096, 4096, 4096]
    for i, pairs in enumerate((134_225_920,) + (58_722_304,) * 3):
        assert fs.attention_layer_forward_flops(cfg, S, i) \
            == 2 * proj * S + 2 * 2 * 28 * 128 * pairs
    # the cores of a step's forward: 1.92 + 3 x 0.84 TFLOP
    cores = sum(fs.attention_layer_forward_flops(cfg, S, i) - 2 * proj * S
                for i in range(4))
    assert cores / 1e12 == pytest.approx(4.45, abs=0.01)


def test_the_experts_by_hand(cfg):
    # 16,384 tokens x 6 picks x 8 held / 64 = 12,288 picks a layer, 1,536
    # an expert: an eighth of what eight such chips would feed it
    assert fs.router_width(cfg) == 64
    assert fs.expected_picks(cfg, S) == 12288
    expert = 3 * 2560 * 768
    assert expert == 5_898_240
    assert fs.moe_layer_forward_flops(cfg, S) \
        == 2 * S * 2560 * 64 + 2 * 12288 * expert
    f, b = fs.moe_reglu_experts_cost(cfg, S)
    assert f == 3 * 2 * 12288 * expert
    # the matrices read twice in bf16 and their gradient written in
    # float32; five (picks, hidden) passes of rows
    assert b == 8 * expert * (2 * 2 + 4) + 5 * 12288 * 2560 * 2
    # bound by FLOPs: 2.21 ms against 0.85 ms a layer
    assert f / 197e12 == pytest.approx(2.207e-3, rel=1e-3)
    assert b / 819e9 == pytest.approx(0.845e-3, rel=1e-2)
    step_f, step_b = moe_reglu_experts_roofline.cost(cfg, S)
    assert (step_f, step_b) == (4 * f, 4 * b)


def test_the_whole_step_by_hand(cfg):
    head = 2 * S * 2560 * 18992
    fwd = fs.lm_forward_flops(cfg, S)
    assert fwd == sum(fs.attention_layer_forward_flops(cfg, S, i)
                      for i in range(4)) \
        + 4 * fs.moe_layer_forward_flops(cfg, S) + head
    # about 9.4 TFLOP forward, 28 a step
    assert fwd / 1e12 == pytest.approx(9.4, abs=0.1)
    assert fs.train_flops_per_record(cfg, S) == 3 * fwd
    builder = harness.load_builder(cfg["family"])
    cell = harness.load_cell(CELL)[0]
    assert builder.train_flops_per_record(cfg, cell) == 3 * fwd
    assert builder.flash_shape(cfg, cell) == (1, 28, S, 128)


class _Op:
    def __init__(self, name, t0, dur, is_mosaic=True):
        self.name, self.t0, self.dur, self.is_mosaic = name, t0, dur, \
            is_mosaic


class _Dev:
    def __init__(self, ops):
        self.ops = ops


class _Trace:
    def __init__(self, ops):
        self.devices = [_Dev(ops)]


def _ctx(cfg, ops):
    cell = harness.load_cell(CELL)[0]
    return {"config": cfg, "cell": cell, "trace": _Trace(ops), "lo": 0.0,
            "hi": 10.0, "peaks": {"bf16_flops_per_s": 197e12,
                                  "hbm_bytes_per_s": 819e9}}


def test_the_forward_readers_find_their_calls_by_name(cfg):
    """Two full and six banded forward calls in the slice (two steps), a
    backward call and an XLA fusion that are not theirs: each reader takes
    its own calls' time and its own pairs, and reads nothing where there
    is no such call, no trace, or another family's keys."""
    ops = [_Op("flash_fwd.3", 1.0, 0.020), _Op("flash_fwd.3", 5.0, 0.020),
           _Op("flash_bwd_dkv.4", 2.0, 0.050),
           _Op("fusion.7", 2.5, 0.5, is_mosaic=False)]
    ops += [_Op(f"flash_band_fwd.{i % 3}", 3.0 + i * 0.1, 0.010)
            for i in range(6)]
    ctx = _ctx(cfg, ops)
    full = 2 * 2 * 28 * 128 * 134_225_920 / 197e12
    band = 2 * 2 * 28 * 128 * 58_722_304 / 197e12
    assert flash16k_fwd_roofline.read(ctx) == pytest.approx(
        100 * full / 0.020)
    assert flash_band16k_fwd_roofline.read(ctx) == pytest.approx(
        100 * band / 0.010)
    assert flash16k_fwd_roofline.read(ctx) < 100
    only_band = _ctx(cfg, ops[4:])
    assert flash16k_fwd_roofline.read(only_band) is None
    assert flash_band16k_fwd_roofline.read(_ctx(cfg, ops[:4])) is None
    assert flash16k_fwd_roofline.read(dict(ctx, trace=None)) is None
    other = harness.load_cell("trinity-mini-train-s8192")[1]
    assert flash_band16k_fwd_roofline.read(dict(ctx, config=other)) is None
    qwen = harness.load_cell("qwen2.5-0.5b-train-s2048")[1]
    assert moe_reglu_experts_roofline.read(dict(ctx, config=qwen)) is None


def test_the_route_ahead_share_reads_nothing_without_the_scope(cfg):
    """No trace, no HLO, or a program whose partition has no
    ``moe_route_ahead`` row: nothing, and no error."""
    ctx = _ctx(cfg, [])
    assert moe_route_ahead_share.read(dict(ctx, trace=None, hlo="")) is None
    assert moe_route_ahead_share.read(dict(
        ctx, step_partition={("moe_route", "forward"): 1.0})) is None
    assert moe_route_ahead_share.read(dict(
        ctx, step_partition={("moe_route_ahead", "forward"): 1.0,
                             ("attn_core", "forward"): 3.0})) == 25.0
