"""The Mamba-2 local part's roofline reader (PR 43): its bytes from shapes,
and that it reads nothing where there is nothing to read."""

import pytest

from benchmark import harness
from benchmark.layer_metrics import mamba_local_roofline as reader

CELL = "nemotron-3-nano-30b-a3b-train-s8192"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def cell_and_cfg():
    return harness.load_cell(CELL)


def test_the_cost_is_the_issues_arithmetic(cell_and_cfg):
    """151,552 bytes a token and ``M`` layer at the published widths; four
    layers over 8,192 tokens are 4.97 GB, 6.06 ms at HBM's rate, and bytes
    bind (the FLOPs would take a hundredth of that)."""
    cell, cfg = cell_and_cfg
    flops, bytes_ = reader.local_cost(cfg, 1)
    assert bytes_ == 151_552
    tokens = cell["batch_size"] * cell["seq_len"]
    f, b = reader.cost(cfg, tokens)
    assert b == 4 * 8192 * 151_552
    assert 6.0e-3 < b / PEAKS["hbm_bytes_per_s"] < 6.1e-3
    assert f / PEAKS["bf16_flops_per_s"] < 0.02 * b / PEAKS["hbm_bytes_per_s"]


def test_the_reader_divides_the_least_time_by_the_layers(cell_and_cfg):
    """Over a partition that charges the layer 52.7 ms a step (the parent's
    reading) it reads 11.5%, and never over 100 while the layer takes at
    least its bytes' time."""
    cell, cfg = cell_and_cfg
    ctx = {"cell": cell, "config": cfg, "peaks": PEAKS,
           "step_partition": {("mamba_local", "forward"): 13.3e-3,
                              ("mamba_local", "recompute"): 13.2e-3,
                              ("mamba_local", "backward"): 26.2e-3,
                              ("ssd_scan", "forward"): 5e-3}}
    assert reader.read(ctx) == pytest.approx(11.5, abs=0.1)
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
        ("ssd_scan", "forward"): 1e-3})) is None
