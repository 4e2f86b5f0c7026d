"""What a CPU can check of ``chip_smoke.py``: that it refuses to run off-TPU,
and that its trainer plumbing (facade routing, the progress-line format it
parses, one compile per site, the mesh checks) still matches the library.
The phases themselves only mean something on the chip."""

import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SPEC = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
chip_smoke = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(chip_smoke)


def test_refuses_to_run_off_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, timeout=120, env=env)
    assert r.returncode != 0
    assert b"default backend is 'cpu'" in r.stderr     # names what it found
    assert b'"ok"' not in r.stdout and b"==" not in r.stdout  # no phase ran


def test_mosaic_calls_splits_forward_from_backward():
    text = "\n".join([
        '%a = bf16[8] custom-call(%x), custom_call_target="tpu_custom_call",'
        ' metadata={op_name="jit(step)/jvp(Sequential)/MHA/pallas_call"}',
        '%b = bf16[8] custom-call(%x), custom_call_target="tpu_custom_call",'
        ' metadata={op_name="jit(step)/transpose(jvp(Sequential))/MHA/p"}',
        '%c = bf16[8] custom-call(%x), custom_call_target="tpu_custom_call",'
        ' metadata={op_name="jit(step)/transpose(jvp(Sequential))/MHA/q"}',
        '%d = f32[8] custom-call(%x), custom_call_target="Sharding"',
    ])
    assert chip_smoke.mosaic_calls(text) == (1, 2)


def test_flash_kernel_timer_runs_each_kernel_alone(monkeypatch):
    """The kernels phase's op-level timer off-chip (interpret mode): one
    positive time for the forward and for the one backward call (dQ, dK
    and dV together), in their own names."""
    import jax.numpy as jnp

    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")
    x = jnp.ones((1, 16, 2, 8), jnp.bfloat16)
    ms = chip_smoke.flash_kernel_ms(x, x, x, runs=2, calls=1)
    assert list(ms) == ["flash_fwd", "flash_bwd_dkv"]
    assert all(t > 0 for t in ms.values())


def test_own_runs_drops_the_run_the_session_cut_off():
    """The timeline phase's run-to-enqueue check is over the profile's own
    runs: the times are those of a v5e profile (PR 24, call 32, r2) in
    which the step of the iteration before the session, cut off at the
    session's start, stood in the slice as a whole run without an enqueue."""
    from benchmark import timeline

    lag = 0.00061
    host = timeline.Host()
    host.spans += [("train.dispatch", 0.0580, 0.0731, {"neval": 3}),
                   ("train.dispatch", 0.0733, 0.0880, {"neval": 4})]
    host.enqueues.update({(509, 0): 0.0716, (527, 0): 0.0867})
    runs = [("jit_step(1)", t0 - lag, t1 - lag, rid) for t0, t1, rid in
            ((0.0404, 0.0536, 490), (0.0717, 0.1278, 509),
             (0.1279, 0.1840, 527))]
    own = chip_smoke.own_runs(runs, host, lag)
    assert [r[3] for r in own] == [509, 527]
    assert all(host.enqueue_of(r[3], 0) is not None for r in own)
    # a run of the profile that lost its enqueue stays, to fail the check
    del host.enqueues[(527, 0)]
    assert [r[3] for r in chip_smoke.own_runs(runs, host, lag)] == [509, 527]


def test_mesh_trainer_plumbing_on_virtual_devices(monkeypatch):
    """The smoke's own train() + check_mesh() over the 8 virtual devices at
    LeNet size: DistriOptimizer through the facade, every progress line
    parsed, train.step compiled ONCE (it was three times before PR 21),
    batch shards on distinct devices, an all-reduce over all of them."""
    import jax
    from bigdl_tpu import nn
    from bigdl_tpu.models import lenet

    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")
    n_dev = len(jax.devices())
    report = {}
    with chip_smoke.Phase("lenet_mesh", report) as ph:
        samples = chip_smoke.image_samples(4 * n_dev, 28, 100, seed=0)
        for s in samples:       # LeNet eats 28x28x1
            s.feature = s.feature[:, :, :1]
        opt, losses, rates = chip_smoke.train(
            lenet.build(10), nn.ClassNLLCriterion(), samples, 2 * n_dev,
            6, lr=0.05, cast=None, distributed=True)
        chip_smoke.one_compile(ph, "train.step")
        chip_smoke.check_mesh(opt, 2 * n_dev, n_dev)
    assert len(losses) == len(rates) == 6
    assert report["lenet_mesh"]["compile_s_by_site"].keys() == {"train.step"}

    # and the placement check is live: the same run, held to "tpu", fails
    monkeypatch.setattr(chip_smoke, "PLATFORM", "tpu")
    with pytest.raises(chip_smoke.SmokeFailure, match="parameter lives on"):
        chip_smoke.train(lenet.build(10), nn.ClassNLLCriterion(), samples,
                         2 * n_dev, 6, lr=0.05, cast=None, distributed=True)
