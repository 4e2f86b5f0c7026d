"""The serve cells' ``correct`` gate can fail: at the rehearsal's tiny sizes
on a CPU, a server that serves the configuration passes, and a server built
with the wrong rope base, judged against the true reference, does not."""

import pytest

from benchmark import harness
from benchmark.kinds import serve

CELL = "qwen2.5-0.5b-serve-steady"


def check(server_cfg, reference_cfg, cell, seed=3):
    server, builder, params = serve.start_server(cell, server_cfg, seed, {})
    try:
        return serve.reference_check(builder, server, params, reference_cfg,
                                     cell, seed)
    finally:
        server.close()


@pytest.fixture(scope="module")
def cell_and_cfg():
    return harness.load_cell(CELL, rehearse=True)


def test_the_served_configuration_passes_and_every_control_fails(cell_and_cfg):
    cell, cfg = cell_and_cfg
    ref = check(cfg, cfg, cell)
    assert ref["ok"], ref
    assert ref["tokens_checked"] == 36
    assert ref["worst_logit_gap"] <= cell["reference"]["logit_gap_tol"]
    assert set(ref["control_fail_share"]) == {
        "no_attention", "no_qkv_bias", "rope_theta_1e4"}
    assert min(ref["control_fail_share"].values()) >= \
        serve.CONTROL_MIN_FAIL_SHARE, ref


def test_a_server_with_the_wrong_rope_base_is_caught(cell_and_cfg):
    cell, cfg = cell_and_cfg
    ref = check(dict(cfg, rope_theta=1e4), cfg, cell)
    assert not ref["ok"], ref
    assert ref["worst_logit_gap"] > cell["reference"]["logit_gap_tol"]


def test_without_the_rescaled_weights_the_check_is_blind(cell_and_cfg):
    """What the review of PR 22 found: with ``build_lm``'s own weights every
    greedy token is the input token whatever attention does, so the broken
    references accept the served tokens too, and the gate says so."""
    cell, cfg = cell_and_cfg
    plain = {k: v for k, v in cfg.items() if k != "serve_weights"}
    ref = check(plain, plain, cell)
    assert ref["worst_logit_gap"] <= cell["reference"]["logit_gap_tol"]
    assert min(ref["control_fail_share"].values()) < \
        serve.CONTROL_MIN_FAIL_SHARE
    assert not ref["ok"], ref
