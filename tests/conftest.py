"""Test config: every test runs on the CPU, with 8 virtual devices for the mesh tests."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_overload_plane():
    """Circuit breakers and the retry budget are process-global and keyed
    by host:port; test servers recycle ports, so a breaker tripped by one
    test's chaos must not fail-fast the next test's first request."""
    yield
    from seaweedfs_tpu.util import backoff, overload

    overload.BREAKERS.reset()
    backoff.configure_retry_budget(None)


REFERENCE_ROOT = "/root/reference"


def reference_available() -> bool:
    return os.path.isdir(REFERENCE_ROOT)
