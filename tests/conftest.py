"""Test configuration: the tests run on the CPU, on a virtual 8-device mesh.

Sharding correctness is tested on XLA's host-platform virtual devices; the
chip itself is exercised by chip_smoke.py. jax may already be imported when
this file runs (a plugin or an earlier import), so the platform is pinned
through jax.config as well as the env var: backends initialize lazily, so
updating jax.config and XLA_FLAGS before the first jax.devices() call still
takes effect.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert len(jax.devices()) >= 8, "expected 8 virtual CPU devices for tests"

# Persist XLA compiles across test runs — the CPU backend pays multi-second
# compiles for the keccak scan programs; the disk cache makes rerun cheap.
from coreth_tpu.utils import enable_compilation_cache  # noqa: E402

enable_compilation_cache()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fault_isolation():
    """Failpoints and the device ladder are process-global; a test that
    arms one and fails before clearing it must not poison the rest of
    the run."""
    yield
    from coreth_tpu import fault
    from coreth_tpu.ops import device

    fault.clear_all()
    device.default_ladder().reset()
