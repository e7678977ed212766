"""Test config: force an 8-device virtual CPU mesh BEFORE jax backends initialize.

Mirrors the reference's test strategy (SURVEY.md §4): distributed features are tested
single-host on a fake multi-device backend (their fake_cpu_device / gloo path; here XLA-CPU
with --xla_force_host_platform_device_count=8).

Tests always run on the CPU: JAX_PLATFORMS is set before jax is imported and
jax_platforms again after it (before any backend starts), so a test process never
loads the chip's library, whatever the caller's environment says.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    """The 8-device virtual CPU mesh the distributed/mesh tests run on.

    The pre-import hook above forces the device count BEFORE jax's backends
    initialize; if some other entry point initialized jax single-device first
    (e.g. a bare pytest invocation of one file with jax already imported), the
    flag cannot retroactively split the backend — skip cleanly instead of
    poisoning every mesh assertion."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 virtual devices: jax initialized before the "
                    "--xla_force_host_platform_device_count=8 hook ran")
    return jax.devices()[:8]
