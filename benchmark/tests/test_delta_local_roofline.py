"""The delta-rule mixers' local part's roofline reader (PR 47): its bytes
from shapes, and that it reads nothing where there is nothing to read."""

import pytest

from benchmark import harness
from benchmark.layer_metrics import delta_local_roofline as reader

CELL = "olmo-hybrid-7b-train-s8192"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def cell_and_cfg():
    return harness.load_cell(CELL)


def test_the_cost_is_the_issues_arithmetic(cell_and_cfg):
    """5 x 5,760 + 8 x 2,880 = 51,840 elements = 103,680 bytes a token and
    ``linear_attention`` layer at the 15 heads of 96 / 192 held; three
    layers over 8,192 tokens are 2.55 GB, 3.11 ms at HBM's rate, and bytes
    bind (the FLOPs would take a hundredth of that)."""
    cell, cfg = cell_and_cfg
    flops, bytes_ = reader.local_cost(cfg, 1)
    assert bytes_ == 103_680
    tokens = cell["batch_size"] * cell["seq_len"]
    f, b = reader.cost(cfg, tokens)
    assert b == 3 * 8192 * 103_680
    assert 3.10e-3 < b / PEAKS["hbm_bytes_per_s"] < 3.12e-3
    assert f / PEAKS["bf16_flops_per_s"] < 0.02 * b / PEAKS["hbm_bytes_per_s"]


def test_the_published_heads_cost_twice_the_held(cell_and_cfg):
    """Every term is a head's: 30 heads are 207,360 bytes a token and
    layer."""
    _, cfg = cell_and_cfg
    whole = dict(cfg, **{k: cfg["published"][k] for k in
                         ("linear_num_key_heads", "linear_num_value_heads")})
    assert reader.local_cost(whole, 1)[1] == 207_360
    assert reader.local_cost(whole, 7)[0] == 2 * reader.local_cost(cfg, 7)[0]


def test_the_reader_divides_the_least_time_by_the_layers(cell_and_cfg):
    """Over a partition that charges the layer 33.9 ms a step (the parent's
    reading: ledger, PR 46) it reads 9.2%, and never over 100 while the
    layer takes at least its bytes' time."""
    cell, cfg = cell_and_cfg
    ctx = {"cell": cell, "config": cfg, "peaks": PEAKS,
           "step_partition": {("delta_local", "forward"): 8.5e-3,
                              ("delta_local", "recompute"): 8.4e-3,
                              ("delta_local", "backward"): 17.0e-3,
                              ("delta_rule", "forward"): 5e-3}}
    assert reader.read(ctx) == pytest.approx(9.18, abs=0.05)
    assert reader.LAYER == "kernels" and reader.UNIT == "%"


def test_the_reader_reads_nothing_where_there_is_nothing(cell_and_cfg):
    """No trace, no HLO, a program without the scope, a family without the
    mixer: ``None``, and no exception."""
    cell, cfg = cell_and_cfg
    empty = {"trace": None, "lo": None, "hlo": "", "cell": cell,
             "config": cfg, "peaks": PEAKS}
    assert reader.read(dict(empty)) is None
    assert reader.read(dict(empty, hlo="optim_update")) is None
    assert reader.read(dict(empty, peaks=None)) is None
    assert reader.read(dict(empty, config={"hidden_size": 2048})) is None
    assert reader.read(dict(empty, step_partition={
        ("delta_rule", "forward"): 1e-3})) is None
