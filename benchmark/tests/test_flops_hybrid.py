"""``flops_hybrid.py`` and the two new readers' ``cost`` against counts made
by hand, at the published widths of the Nemotron cell."""

import pytest

from benchmark import flops_hybrid as fh, harness
from benchmark.layer_metrics import moe_experts_roofline, ssd_scan_roofline

CELL = "nemotron-3-nano-30b-a3b-train-s8192"


@pytest.fixture(scope="module")
def cfg():
    return harness.load_cell(CELL)[1]


def test_one_mamba_layer_by_hand(cfg):
    s = 8192
    # in-projection 2688 x (4096 z + 6144 xBC + 64 dt), out-projection
    # 4096 x 2688: 38,707,200 MACs a token
    proj = 2688 * (4096 + 4096 + 2 * 8 * 128 + 64) + 4096 * 2688
    assert proj == 38_707_200
    # the scan, a token: C B^T over the causal half of a 128-chunk in 8
    # groups of 128 (8*128*128/2 MACs), (C B^T * decay) (dt x) over the
    # same half for 64 heads of 64 (64*64*128/2), the chunk state and its
    # read-out (2 x 64*64*128)
    scan = 8 * 128 * 128 // 2 + 64 * 64 * 128 // 2 + 2 * 64 * 64 * 128
    assert scan == 1_376_256
    assert fh.ssd_scan_forward_flops(cfg, s) == 2 * scan * s
    assert fh.mamba_layer_forward_flops(cfg, s) == 2 * (proj + scan) * s


def test_one_expert_layer_by_hand(cfg):
    s = 8192
    # 8,192 tokens x 6 picks x 8 held / 128 routed = 3,072 picks land here
    assert fh.expected_picks(cfg, s) == 3072
    router = 2688 * 128 * s
    shared = 2 * 2688 * 3712 * s
    routed = 2 * 2688 * 1856 * 3072
    assert fh.moe_layer_forward_flops(cfg, s) == 2 * (router + shared
                                                      + routed)
    # the shared expert is 5.3x the held routed experts' work at this load
    assert shared / routed == pytest.approx(5.33, rel=1e-2)


def test_the_attention_layer_and_the_whole_step_by_hand(cfg):
    s = 8192
    proj = 2688 * (32 + 2 + 2) * 128 + 32 * 128 * 2688      # q, k, v; out
    attn = 32 * 128 * 2 * s // 2                            # causal half
    assert fh.attention_layer_forward_flops(cfg, s) == 2 * (proj + attn) * s
    head = 2 * 2688 * 16384 * s
    fwd = 4 * fh.mamba_layer_forward_flops(cfg, s) \
        + 4 * fh.moe_layer_forward_flops(cfg, s) \
        + fh.attention_layer_forward_flops(cfg, s) + head
    assert fh.train_flops_per_record(cfg, s) == 3 * fwd
    assert fh.train_flops_per_record(cfg, s) == pytest.approx(17.57e12,
                                                              rel=1e-3)


def test_the_scans_cost_is_bound_by_bytes(cfg):
    f, b = ssd_scan_roofline.cost(cfg, 8192)
    assert f == 4 * 3 * fh.ssd_scan_forward_flops(cfg, 8192)
    # a token a layer: x, y, dy, dx and x again (5 x 4096) and B, C three
    # times over (6 x 1024) in bf16, dt and d(dt) three float32 rows of 64
    assert b == 4 * 8192 * ((5 * 4096 + 6 * 1024) * 2 + 3 * 64 * 4)
    assert b / 819e9 > f / 197e12


def test_the_held_experts_cost_counts_the_picks_that_land_here(cfg):
    f, b = moe_experts_roofline.cost(cfg, 8192)
    assert f == 4 * 3 * 2 * 3072 * 2 * 2688 * 1856
    weights = 8 * 2 * 2688 * 1856                   # 79.8M held a layer
    assert b == 4 * (weights * (2 * 2 + 4) + 5 * 3072 * 2688 * 2)
    # FLOPs and bytes nearly tie: 0.93 ms against 0.88 ms a layer
    assert f / 197e12 == pytest.approx(1.06 * b / 819e9, rel=2e-2)
    # at the dispatch's static bound (6 x 8,192 rows) it would be 16 x
    assert 8192 * 6 / fh.expected_picks(cfg, 8192) == 16
