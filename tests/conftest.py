"""Test config: CPU backend with 8 virtual devices so SPMD/sharding
tests run without TPU hardware (SURVEY.md §4: the reference CI runs 2-rank
MPI on CPU; our analogue is an 8-device virtual CPU mesh). Set before jax
initializes a backend — `JAX_PLATFORMS` is honoured as given.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

assert len(jax.devices()) == 8, jax.devices()


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")
