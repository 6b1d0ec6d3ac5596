"""Test configuration: run everything on a virtual 8-device CPU mesh so
multi-chip sharding paths (data/feature/voting-parallel learners) are
exercised without TPU pod hardware. Must run before jax is imported."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def examples_dir():
    for cand in ("/root/repo/examples", "/root/reference/examples"):
        if os.path.isdir(cand):
            return cand
    pytest.skip("no examples directory")


@pytest.fixture(scope="session")
def reference_examples():
    """The reference checkout's example datasets. Hosts without the
    read-only /root/reference mirror must skip the parity/CLI legs
    loudly — an absent checkout is an environment gap, not a code
    failure, and should never surface as np.loadtxt/shutil errors."""
    path = "/root/reference/examples"
    if not os.path.isdir(path):
        pytest.skip("reference examples not present at "
                    "/root/reference/examples (environment lacks the "
                    "reference checkout; not a code failure)")
    return path


@pytest.fixture
def rng():
    return np.random.RandomState(42)
