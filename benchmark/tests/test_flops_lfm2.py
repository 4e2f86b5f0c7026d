"""``flops_lfm2.py`` and the new readers' costs against counts made by hand,
at the published widths of the LFM2 cell; that the accepted readers this
cell is listed under read its keys right."""

import pytest

from benchmark import flops, flops_lfm2 as fl, harness
from benchmark.layer_metrics import (lm_head_ce_roofline,
                                     moe_gated_experts_roofline,
                                     short_conv_local_roofline,
                                     short_conv_local_share,
                                     short_conv_proj_share)

CELL = "lfm2-24b-a2b-train-s8192"
S, B = 8192, 4
T = S * B


@pytest.fixture(scope="module")
def cfg():
    return harness.load_cell(CELL)[1]


def test_the_convolution_mixer_by_hand(cfg):
    # in 2,048 x 6,144 and out 2,048 x 2,048: 16,777,216 MACs a token,
    # 33.5 MFLOP
    macs = 2048 * 6144 + 2048 * 2048
    assert macs == 16_777_216
    assert fl.short_conv_proj_forward_flops(cfg, 1) == 2 * macs == 33_554_432
    # the four mixers' products of a step's forward: 4.40 TFLOP
    assert 4 * fl.short_conv_proj_forward_flops(cfg, T) / 1e12 \
        == pytest.approx(4.398, abs=0.001)
    f, b = fl.short_conv_local_cost(cfg, T)
    # a token a channel: B * x, three multiplies and two adds, C * c = 7
    # forward, 21 with the backward
    assert f == 21 * 2048 * T
    # forward reads 3E and writes E; backward reads 3E and E and writes 3E:
    # 11 x 2,048 elements of 2 bytes = 45,056 a token
    assert b == 45_056 * T == 1_476_395_008
    # bound by bytes on the v5e: 1.80 ms against 0.007 ms a layer
    assert b / 819e9 == pytest.approx(1.803e-3, rel=1e-3)
    assert f / 197e12 == pytest.approx(7.15e-6, rel=1e-2)
    # the reader's cost: the four ``conv`` layers of the stage, 5.9 GB,
    # 7.2 ms a step at the least
    rf, rb = short_conv_local_roofline.cost(cfg, T)
    assert (rf, rb) == (4 * f, 4 * b)
    assert rb / 819e9 == pytest.approx(7.21e-3, rel=1e-3)


def test_the_attention_layer_by_hand(cfg):
    assert fl.head_dim(cfg) == 64
    # q 2,048 x 2,048, k and v 2,048 x 512 each, out 2,048 x 2,048:
    # 10,485,760 MACs a token
    proj = 2048 * (2048 + 512 + 512) + 2048 * 2048
    assert proj == 10_485_760
    pairs = S * (S + 1) // 2
    assert fl.band_pairs(S) == pairs == 33_558_528
    assert fl.attention_layer_forward_flops(cfg, S) \
        == 2 * proj * S + 2 * 2 * 32 * 64 * pairs
    # a step's forward: the core 1.10 TFLOP, its projections 0.69
    assert B * 2 * 2 * 32 * 64 * pairs / 1e12 == pytest.approx(1.0996,
                                                                abs=1e-3)
    assert 2 * proj * T / 1e12 == pytest.approx(0.687, abs=1e-3)
    # the flash call the accepted readers reckon: (4, 32, 8192, 64)
    builder = harness.load_builder(cfg["family"])
    cell = harness.load_cell(CELL)[0]
    shape = builder.flash_shape(cfg, cell)
    assert shape == (4, 32, 8192, 64)
    f, b = flops.flash_forward_cost(*shape)
    assert f == pytest.approx(B * 2 * 2 * 32 * 64 * pairs, rel=2e-4)
    assert b == 4 * 128 * S * 64 * 2 + 4 * 128 * S


def test_the_feed_forwards_by_hand(cfg):
    dense = 3 * 2048 * 11776
    assert dense == 72_351_744
    assert fl.dense_layer_forward_flops(cfg, T) == 2 * T * dense
    assert fl.dense_layer_forward_flops(cfg, T) / 1e12 \
        == pytest.approx(4.742, abs=1e-3)
    # 32,768 tokens x 4 picks x 8 held / 64 = 16,384 picks a layer, 2,048
    # an expert: an eighth of what eight such chips would feed it
    assert fl.router_width(cfg) == 64
    assert fl.expected_picks(cfg, T) == 16384
    expert = 3 * 2048 * 1536
    assert expert == 9_437_184
    assert fl.moe_layer_forward_flops(cfg, T) \
        == 2 * T * 2048 * 64 + 2 * 16384 * expert
    # the four layers' held experts of a step's forward: 1.24 TFLOP
    assert 4 * 2 * 16384 * expert / 1e12 == pytest.approx(1.237, abs=1e-3)


def test_the_accepted_readers_read_this_familys_keys_right(cfg):
    """``moe_gated_experts_roofline`` (the ``afmoe`` family's reader) finds
    4 expert layers and 16,384 picks a layer here; ``lm_head_ce_roofline``
    one pass over the held rows."""
    f, b = moe_gated_experts_roofline.cost(cfg, T)
    one_f, one_b = fl.moe_gated_experts_cost(cfg, T)
    assert (f, b) == (4 * one_f, 4 * one_b)
    assert one_f == 3 * 3 * 16384 * 2 * 2048 * 1536
    assert one_b == 8 * 3 * 2048 * 1536 * 8 + 5 * 16384 * 2048 * 2
    # bound by FLOPs: 4.71 ms against 1.15 ms a layer
    assert one_f / 197e12 == pytest.approx(4.709e-3, rel=1e-3)
    assert one_b / 819e9 == pytest.approx(1.147e-3, rel=1e-2)
    hf, _ = lm_head_ce_roofline.cost(T, cfg["hidden_size"],
                                     cfg["vocab_size"])
    assert hf == 6 * T * 2048 * 8192


def test_the_model_by_hand(cfg):
    """13.3 TFLOP forward a step of four records, the tied head once."""
    per = fl.lm_forward_flops(cfg, S)
    head = 2 * S * 2048 * 8192
    want = (4 * fl.short_conv_proj_forward_flops(cfg, S)
            + fl.attention_layer_forward_flops(cfg, S)
            + fl.dense_layer_forward_flops(cfg, S)
            + 4 * fl.moe_layer_forward_flops(cfg, S) + head)
    assert per == want
    assert B * head / 1e12 == pytest.approx(1.0995, abs=1e-3)
    assert B * per / 1e12 == pytest.approx(13.30, abs=0.03)
    assert fl.train_flops_per_record(cfg, S) == 3 * per
    builder = harness.load_builder(cfg["family"])
    assert builder.train_flops_per_record(cfg, {"seq_len": S}) == 3 * per


def test_the_new_readers_read_nothing_where_there_is_nothing(cfg):
    """No trace, no HLO, a program without the scopes, a family without
    the mixer: ``None``, and no exception."""
    empty = {"trace": None, "lo": None, "hlo": "", "cell": {"seq_len": S,
             "batch_size": B}, "config": cfg, "peaks": {
                 "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    for reader in (short_conv_proj_share, short_conv_local_share,
                   short_conv_local_roofline):
        assert reader.read(dict(empty)) is None
        assert reader.read(dict(empty, hlo="optim_update")) is None
        assert reader.UNIT == "%"
    assert short_conv_local_roofline.read(
        dict(empty, config={"hidden_size": 2048})) is None
