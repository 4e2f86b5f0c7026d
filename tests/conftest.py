"""Test config: force an 8-device virtual CPU platform BEFORE jax imports.

Mirrors the reference's trick of simulating a 4-node cluster inside one JVM
(``DistriOptimizerSpec.scala:40-42`` with ``Engine.init(4, 4, true)``): here
``xla_force_host_platform_device_count=8`` fakes an 8-chip mesh on CPU so
every sharding/collective path compiles and runs without TPU hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# keep Engine.init()'s launch-env advisory quiet in test logs; the check
# itself is covered explicitly by tests/test_core.py::TestEngineEnvCheck
os.environ.setdefault("BIGDL_TPU_DISABLE_ENV_CHECK", "1")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")


@pytest.fixture(autouse=True)
def _reset_engine_and_seed():
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.rng import manual_seed
    Engine.reset()
    manual_seed(1)
    yield
    Engine.reset()
