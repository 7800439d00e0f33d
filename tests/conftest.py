"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh (SURVEY.md §7) so multi-chip
sharding paths are exercised without TPU hardware. The suite is hermetic: the
platform is pinned here as well as by the JAX_PLATFORMS=cpu the tier-1 command
exports, so a bare `pytest tests/` on a machine with a chip still runs on CPU.
"""

import os
import sys

# XLA_FLAGS is read at backend-init time, so mutating it here still works.
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture
def make_df():
    import daft_tpu

    def _make(data):
        return daft_tpu.from_pydict(data)

    return _make
