"""``flops_olmo_hybrid.py`` and the new readers' costs against counts made
by hand, at the published widths of the Olmo-Hybrid cell's share; that the
accepted readers this cell is listed under read its keys right."""

import pytest

from benchmark import flops, flops_olmo_hybrid as fl, harness
from benchmark.layer_metrics import (delta_local_share, delta_proj_share,
                                     delta_rule_roofline, delta_rule_share,
                                     lm_head_ce_roofline)

CELL = "olmo-hybrid-7b-train-s8192"
S = T = 8192


@pytest.fixture(scope="module")
def cfg():
    return harness.load_cell(CELL)[1]


def test_the_linear_layer_by_hand(cfg):
    # 15 heads held: q and k 1,440 wide, v and the gate 2,880, beta and
    # the decay's input 15 each: 8,670 columns in, 2,880 out
    wide = 2 * 15 * 96 + 2 * 15 * 192 + 2 * 15
    assert wide == 8670
    macs = 3840 * wide + 2880 * 3840
    assert macs == 44_352_000
    assert fl.delta_proj_forward_flops(cfg, 1) == 2 * macs
    # the three mixers' products of a step's forward: 2.18 TFLOP
    assert 3 * fl.delta_proj_forward_flops(cfg, T) / 1e12 \
        == pytest.approx(2.180, abs=1e-3)
    # the recurrence itself: k^T S, the rank-1 correction and write, S^T q
    # = 6 x 96 x 192 a head = 110,592; 1.66 MFLOP a token at 15 heads
    assert fl.delta_rule_forward_flops(cfg, 1) == 15 * 110_592 == 1_658_880
    assert fl.linear_layers(cfg) == 3


def test_the_recurrences_cost_by_hand(cfg):
    f, b = fl.delta_rule_cost(cfg, T)
    # forward + twice that backward
    assert f == 3 * 1_658_880 * T
    # a head and token: q, k, v read and o written forward (96 + 96 + 192 +
    # 192 bf16), those four and o's cotangent read and three cotangents
    # written backward (576 + 192 + 384), g and beta and their cotangents
    # as floats three times: 3,456 + 24 = 3,480 B; 52,200 a token
    per_head = (96 + 96 + 192 + 192 + 576 + 192 + 384) * 2 + 6 * 4
    assert per_head == 3480
    assert b == 15 * per_head * T == 52_200 * T == 427_622_400
    # bound by bytes on the v5e: 0.522 ms against 0.207 ms a layer
    assert b / 819e9 == pytest.approx(5.221e-4, rel=1e-3)
    assert f / 197e12 == pytest.approx(2.069e-4, rel=1e-3)
    # the reader's cost: the three linear layers, 1.28 GB, 1.57 ms a step
    rf, rb = delta_rule_roofline.cost(cfg, T)
    assert (rf, rb) == (3 * f, 3 * b)
    assert rb / 819e9 == pytest.approx(1.566e-3, rel=1e-3)


def test_the_attention_layer_by_hand(cfg):
    # q, k and v 1,920 wide each (15 heads of 128), out 1,920 -> 3,840
    proj = 3840 * 3 * 1920 + 1920 * 3840
    assert proj == 29_491_200
    pairs = S * (S + 1) // 2
    assert fl.band_pairs(S) == pairs == 33_558_528
    assert fl.attention_layer_forward_flops(cfg, S) \
        == 2 * proj * S + 2 * 2 * 15 * 128 * pairs
    # a step's forward: the core 0.258 TFLOP, its projections 0.483
    assert 2 * 2 * 15 * 128 * pairs / 1e12 == pytest.approx(0.2577, abs=1e-3)
    assert 2 * proj * S / 1e12 == pytest.approx(0.4832, abs=1e-3)
    # the flash call the accepted readers reckon: (1, 15, 8192, 128)
    builder = harness.load_builder(cfg["family"])
    cell = harness.load_cell(CELL)[0]
    shape = builder.flash_shape(cfg, cell)
    assert shape == (1, 15, 8192, 128)
    f, b = flops.flash_forward_cost(*shape)
    assert f == pytest.approx(2 * 2 * 15 * 128 * pairs, rel=2e-4)
    assert b == 4 * 15 * S * 128 * 2 + 15 * S * 4


def test_the_model_by_hand(cfg):
    """12.07 TFLOP forward a step of one record, 36.2 with the backward;
    the MLP, held whole beside half the heads, is 69% of it."""
    mlp = 3 * 3840 * 11008
    assert mlp == 126_812_160
    assert fl.mlp_forward_flops(cfg, T) == 2 * T * mlp
    assert 4 * 2 * T * mlp / 1e12 == pytest.approx(8.311, abs=1e-3)
    per = fl.lm_forward_flops(cfg, S)
    head = 2 * S * 3840 * 12544
    want = (3 * (fl.delta_proj_forward_flops(cfg, S)
                 + fl.delta_rule_forward_flops(cfg, S))
            + fl.attention_layer_forward_flops(cfg, S)
            + 4 * fl.mlp_forward_flops(cfg, S) + head)
    assert per == want
    assert head / 1e12 == pytest.approx(0.7892, abs=1e-3)
    assert per / 1e12 == pytest.approx(12.06, abs=0.02)
    assert fl.train_flops_per_record(cfg, S) == 3 * per
    assert 3 * per / 1e12 == pytest.approx(36.2, abs=0.1)
    builder = harness.load_builder(cfg["family"])
    assert builder.train_flops_per_record(cfg, {"seq_len": S}) == 3 * per


def test_the_accepted_readers_read_this_familys_keys_right(cfg):
    """``lm_head_ce_roofline``: one pass over the held rows at the
    family's hidden size."""
    hf, _ = lm_head_ce_roofline.cost(T, cfg["hidden_size"],
                                     cfg["vocab_size"])
    assert hf == 6 * T * 3840 * 12544


def test_the_new_readers_read_nothing_where_there_is_nothing(cfg):
    """No trace, no HLO, a program without the scopes, a family without
    the mixer: ``None``, and no exception."""
    empty = {"trace": None, "lo": None, "hlo": "", "cell": {"seq_len": S,
             "batch_size": 1}, "config": cfg, "peaks": {
                 "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    for reader in (delta_proj_share, delta_local_share, delta_rule_share,
                   delta_rule_roofline):
        assert reader.read(dict(empty)) is None
        assert reader.read(dict(empty, hlo="optim_update")) is None
        assert reader.UNIT == "%"
    assert delta_rule_roofline.read(
        dict(empty, config={"hidden_size": 3840})) is None
    assert delta_rule_roofline.LAYER == "kernels"
    assert delta_rule_share.LAYER == "model step"
