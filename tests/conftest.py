"""Test configuration: run everything on a virtual 8-device CPU mesh so
multi-chip sharding logic is exercised without TPU hardware (the driver's
dryrun_multichip uses the same trick)."""

import os

# Tests run on the CPU with eight virtual devices. Set in the environment,
# before jax is imported, so that subprocesses the tests start inherit it.
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Give every test fresh default programs + scope + name counters."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework
    from paddle_tpu.core import scope as scope_mod
    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    framework.reset_unique_name()
    scope_mod.reset_global_scope()
    from paddle_tpu.v2 import config_helpers
    config_helpers._reset_config()
    np.random.seed(123)
    yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (wheel builds, big configs)")
