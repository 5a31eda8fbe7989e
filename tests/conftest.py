"""Test configuration.

Must run before jax is imported anywhere: force the CPU platform with 8
virtual devices so multi-chip sharding tests run on a single host
(≙ the reference testing MPI paths with `mpirun -np 4 / -np 7` on one
machine, scripts/mpi_test.sh), and enable x64 so differential tests can
use the reference's double-precision tolerances (tests/mttkrp_test.c:25-30).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

# The persistent compile cache stays OFF in the test processes, before
# anything can apply the default (utils/env.py:apply_compile_cache):
# on this jaxlib a DESERIALIZED multi-device (8-virtual-device sharded)
# CPU executable corrupts the heap on execution — malloc() abort inside
# pxla — and the suite runs the sharded paths constantly.  The fleet
# chaos soak scopes JAX_COMPILATION_CACHE_DIR to its replica daemons
# (single-device jobs only).
jax.config.update("jax_enable_compilation_cache", False)
jax.config.update("jax_enable_x64", True)

import numpy as np
import pytest

from tests import gen


def pytest_configure(config):
    # the tier-1 gate runs `-m 'not slow'` (ROADMAP.md); register the
    # marker so slow-tier tests don't warn as unknown
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 `-m 'not slow'` "
                   "gate (full sweeps, 3-replica interleavings)")


@pytest.fixture(scope="session")
def tensors_dir(tmp_path_factory):
    """Generate the fixture tensor files once per session."""
    d = tmp_path_factory.mktemp("tensors")
    gen.write_fixtures(d)
    return d


@pytest.fixture(params=["small", "med", "small4", "med4", "med5"])
def any_tensor(request):
    """All fixture tensors as in-memory COO (≙ tests/tensors/*.tns sweep)."""
    return gen.fixture_tensor(request.param)
