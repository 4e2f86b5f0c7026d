"""``flops_mla.py`` and the new readers' costs against counts made by hand,
at the published widths of the JoyAI-LLM-Flash cell."""

import pytest

from benchmark import flops, flops_mla as fm, harness
from benchmark.layer_metrics import (flash_mla_fwd_roofline, flash_mla_share,
                                     mla_proj_share, mtp_share)

CELL = "joyai-llm-flash-train-s8192"
S = 8192
PAIRS = 33_558_528


@pytest.fixture(scope="module")
def cfg():
    return harness.load_cell(CELL)[1]


def test_the_latent_forward_call_by_hand(cfg):
    assert fm.band_pairs(S) == S * (S + 1) // 2 == PAIRS
    assert fm.head_sizes(cfg) == (192, 128)
    f, b = fm.flash_mla_forward_cost(1, 32, S, 192, 128)
    # QK^T contracts 192 and PV 128, a multiply-add 2 FLOPs, a pair
    assert f == 32 * PAIRS * (2 * 192 + 2 * 128) == 687_278_653_440
    # q and k in at 192, v in and o out at 128, bf16, the float32 LSE row
    assert b == 32 * S * (2 * 192 + 2 * 128) * 2 + 4 * 32 * S
    # compute-bound on the v5e: 3.49 ms of FLOPs against 0.41 ms of bytes
    assert f / 197e12 == pytest.approx(3.489e-3, rel=1e-3)
    assert b / 819e9 == pytest.approx(0.411e-3, rel=1e-2)
    # with equal heads it is the accepted count, diagonal included
    same, bytes_ = fm.flash_mla_forward_cost(1, 32, S, 128, 128)
    assert bytes_ == flops.flash_forward_cost(1, 32, S, 128)[1]
    assert same == pytest.approx(flops.flash_forward_cost(1, 32, S, 128)[0],
                                 rel=2e-4)


def test_one_latent_attention_layer_by_hand(cfg):
    # Wqa 2048 x 1536, Wqb 1536 x 32 x 192, Wkva 2048 x (512 + 64),
    # Wkvb 512 x 32 x (128 + 128), Wo 32 x 128 x 2048
    proj = 2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert proj == fm.latent_projection_params(cfg) == 26_345_472
    assert fm.attention_layer_forward_flops(cfg, S) \
        == 2 * proj * S + 687_278_653_440
    # the issue's 432 and 687 GFLOP a layer: attention 61% of the layer
    assert 2 * proj * S == pytest.approx(431.6e9, rel=1e-3)


def test_the_feed_forwards_by_hand(cfg):
    assert fm.router_width(cfg) == 256 and cfg["n_routed_experts"] == 16
    # 8 of 256 picked, 16 held: half a pick a token lands here
    assert fm.expected_picks(cfg, S) == S * 8 * 16 / 256 == 4096
    assert fm.dense_layer_forward_flops(cfg, S) == 2 * S * 3 * 2048 * 7168
    expert = 3 * 2048 * 768
    assert fm.moe_layer_forward_flops(cfg, S) \
        == 2 * S * 2048 * 256 + 2 * S * expert + 2 * 4096 * expert


def test_the_model_and_its_module_by_hand(cfg):
    attn = fm.attention_layer_forward_flops(cfg, S)
    moe = fm.moe_layer_forward_flops(cfg, S)
    head = 2 * S * 2048 * 16160
    assert fm.head_forward_flops(cfg, S) == head
    t = S - 1       # the positions that have a next token
    module = 2 * t * 4096 * 2048 + fm.attention_layer_forward_flops(cfg, t) \
        + fm.moe_layer_forward_flops(cfg, t) + 2 * t * 2048 * 16160
    assert fm.mtp_forward_flops(cfg, S) == module
    assert fm.mtp_forward_flops(dict(cfg, num_nextn_predict_layers=0), S) == 0
    whole = 5 * attn + fm.dense_layer_forward_flops(cfg, S) + 4 * moe \
        + head + module
    assert fm.lm_forward_flops(cfg, S) == whole
    assert fm.train_flops_per_record(cfg, S) == 3 * whole
    # six latent-attention cores are 44% of the model's FLOPs, the module
    # (a layer, a projection and a second pass through the head) a fifth
    assert 6 * 687_278_653_440 / whole == pytest.approx(0.44, abs=0.01)
    assert module / whole == pytest.approx(0.207, abs=0.005)
    # 27.8 TFLOP a record: 141 ms a step at the v5e's peak
    assert 3 * whole == pytest.approx(27.84e12, rel=1e-3)


def test_the_readers_read_nothing_where_there_is_nothing():
    """A program without the kernels' names or the scopes (the parent's),
    or a run without a trace: None, never an exception."""
    empty = {"trace": None, "lo": None, "hi": None, "hlo": "", "busy_s": 0.0,
             "peaks": None, "config": {}, "cell": {}}
    for reader in (flash_mla_share, flash_mla_fwd_roofline, mla_proj_share,
                   mtp_share):
        assert reader.read(empty) is None
        assert reader.UNIT == "%"
    assert flash_mla_share.mla_ops(empty) == []
