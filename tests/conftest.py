"""Test harness: force an 8-device virtual CPU mesh.

Must run before anything imports jax: we both inject the XLA host-device
flag and explicitly pin the platform to cpu, so the suite never takes a
chip a serving or training process may hold. This is the envtest
equivalent for the compute path (SURVEY.md §4: hermetic tiers below the
top); the control-plane tests use the in-memory apiserver instead.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, "conftest failed to create 8 virtual CPU devices"
    return devs[:8]
