"""``flops_ouro.py`` and the two new readers' costs against counts made by
hand, at the published widths of the Ouro cell's cut; that the accepted
readers this cell is listed under read its keys right."""

import pytest

from benchmark import flops, flops_ouro as fl, harness
from benchmark.layer_metrics import (lm_head_ce_weighted_roofline,
                                     loop_exit_share)

CELL = "ouro-2.6b-train-s4096"
T, P = 4096, 4


@pytest.fixture(scope="module")
def cfg():
    return harness.load_cell(CELL)[1]


def test_the_parameters_by_hand(cfg):
    # a layer: q, k, v and out at 2,048 x 2,048, gate, up and down at
    # 2,048 x 5,632, four norm weights
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert layer == 51_380_224 == fl.layer_parameters(cfg)
    assert layer + 4 * 2048 == 51_388_416
    table = 49152 * 2048
    assert table == 100_663_296
    # eight layers, the embedding and the untied head, the final norm, the
    # gate's row and its bias
    assert fl.parameters(cfg) == 8 * 51_388_416 + 2 * table + 2048 + 2049 \
        == 612_438_017


def test_a_step_by_hand(cfg):
    # a layer application, forward + backward: 6 FLOPs a parameter and
    # token, and the causal cores over 8,390,656 pairs a head
    products = 6 * 51_380_224 * T
    assert products / 1e12 == pytest.approx(1.263, abs=1e-3)
    pairs = T * (T + 1) // 2
    assert pairs == 8_390_656
    cores = 3 * 2 * 2 * 16 * 128 * pairs
    assert cores / 1e12 == pytest.approx(0.206, abs=1e-3)
    assert 3 * fl.layer_forward_flops(cfg, T) == products + cores
    head = 6 * T * 2048 * 49152
    assert head / 1e12 == pytest.approx(2.474, abs=1e-3)
    gate = 6 * T * 2048
    # every pass counts: 32 layer applications, four heads, four gates
    want = P * (8 * (products + cores) + head + gate)
    assert fl.train_flops_per_record(cfg, T) == want
    assert want / 1e12 == pytest.approx(56.9, abs=0.05)
    # one pass is the dense decoder's count at these keys
    one = flops.lm_train_flops_per_record(
        dict(cfg, hidden_size=2048, num_attention_heads=16), T)
    assert (want - P * gate) / P == pytest.approx(
        one + 8 * 3 * 2 * 2 * 16 * 128 * T / 2, rel=1e-12)


def test_the_heads_cost_over_all_passes_by_hand(cfg):
    f, b = fl.lm_head_ce_cost(cfg, T, P)
    rows = P * T
    assert f == 6 * rows * 2048 * 49152 == 9_895_604_649_984
    # h read twice and dh written (bf16), W read twice (bf16), dW written
    # once in float32, targets, weights and losses as words
    assert b == 3 * rows * 2048 * 2 + 2 * 49152 * 2048 * 2 \
        + 49152 * 2048 * 4 + 3 * rows * 4 == 1_006_829_568
    # compute-bound on the v5e: 50.2 ms against 1.23 ms
    assert f / 197e12 == pytest.approx(5.023e-2, rel=1e-3)
    assert b / 819e9 == pytest.approx(1.229e-3, rel=1e-3)
    # the same work as one call or as one a pass: FLOPs add up, and only
    # the head's own bytes would be paid P times
    f1, b1 = fl.lm_head_ce_cost(cfg, T, 1)
    assert P * f1 == f and b < P * b1


def test_the_readers_read_nothing_without_a_trace_or_a_loop(cfg):
    ctx = {"cell": {"batch_size": 1, "seq_len": T}, "config": cfg,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert lm_head_ce_weighted_roofline.read(dict(ctx)) is None
    assert loop_exit_share.read(dict(ctx)) is None
    flat = {k: v for k, v in cfg.items() if k != "total_ut_steps"}
    assert lm_head_ce_weighted_roofline.read(dict(ctx, config=flat)) is None


def test_the_weighted_roofline_is_cost_over_the_scopes_seconds(cfg):
    # 62.8 ms of lm_head_ce a step for 50.23 ms of FLOPs: 80%
    ctx = {"cell": {"batch_size": 1, "seq_len": T}, "config": cfg,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "step_partition": {("lm_head_ce", "forward"): 0.0428,
                              ("lm_head_ce", "backward"): 0.0200,
                              ("mlp", "forward"): 0.1,
                              ("loop_exit", "forward"): 0.0005,
                              ("loop_exit", "backward"): 0.0005}}
    assert lm_head_ce_weighted_roofline.read(ctx) == pytest.approx(
        100 * 5.0231e-2 / 0.0628, rel=1e-3)
    assert loop_exit_share.read(ctx) == pytest.approx(
        100 * 0.001 / 0.1638, rel=1e-6)


def test_the_accepted_readers_read_the_cells_keys(cfg):
    builder = harness.load_builder(cfg["family"])
    cell = harness.load_cell(CELL)[0]
    assert builder.flash_shape(cfg, cell) == (1, 16, 4096, 128)
    assert builder.train_flops_per_record(cfg, cell) \
        == fl.train_flops_per_record(cfg, T)
    manifest = harness.load_manifest()
    listed = {e["name"] for e in manifest["per_layer"]
              if CELL in e.get("workloads", ())}
    assert {"loop_exit_share", "lm_head_ce_weighted_roofline",
            "flash_fwd_roofline", "lm_head_ce_share", "model_flops_util",
            "step_device_ms", "hbm_peak_gb.train"} <= listed
    # its cost is one pass a step: this cell runs four
    assert "lm_head_ce_roofline" not in listed
