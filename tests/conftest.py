"""Test-wide setup: run JAX on a virtual 8-device CPU mesh.

Must run before any jax backend is initialized, so it lives at the top
of conftest. The chip is benchmark/run.py's and chip_smoke.py's; tests
validate sharding logic on virtual devices per the multi-chip test strategy.
"""
import os

# The test suite is defined to run on a virtual 8-device CPU mesh (the
# opt-in CSTPU_TEST_TPU=1 mode leaves jax on whatever device it finds).
if os.environ.get("CSTPU_TEST_TPU") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

if os.environ.get("CSTPU_TEST_TPU") != "1":
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)

# Persistent compilation cache: the BLS pairing programs take ~1 min each to
# compile on the CPU backend; caching them across pytest processes turns
# repeat runs into millisecond cache hits.
from consensus_specs_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()

import pytest  # noqa: E402

# Accelerated-backend mode: route the spec's permutation and full-state-root
# hooks through the batched/bulk kernels for the WHOLE corpus run. Used by
# the mainnet CI job (make citest-mainnet), where 64-slot epochs of
# recursive per-slot Merkleization are otherwise minutes per scenario —
# and doubling as continuous differential coverage of the hooks (both are
# bit-equality-tested against the recursive oracles in their own suites).
if os.environ.get("CSTPU_ACCEL") == "1":
    from consensus_specs_tpu.models.phase0.helpers import install_bulk_state_root
    from consensus_specs_tpu.ops.shuffle import install_device_shuffler
    install_bulk_state_root()
    install_device_shuffler()


# Line-coverage collection (tools/cov.py, stdlib sys.monitoring): opt-in
# because the artifact write belongs to the CI lane (make citest-cov), not
# every local run. Near-zero steady overhead (per-location DISABLE).
import sys as _sys

if os.environ.get("CSTPU_COV") == "1" and hasattr(_sys, "monitoring"):
    import importlib.util
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    _cspec = importlib.util.spec_from_file_location(
        "cstpu_cov", os.path.join(_root, "tools", "cov.py"))
    _cov = importlib.util.module_from_spec(_cspec)
    _cspec.loader.exec_module(_cov)
    _cov.start(os.path.join(_root, "consensus_specs_tpu"))


def pytest_addoption(parser):
    parser.addoption(
        "--preset", action="store", default="minimal",
        help="constant preset to run spec tests under (minimal/mainnet)",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running (pairing corpus / state-to-state) — excluded "
        "from the default `make test` lane, included in `make citest`")


@pytest.fixture(scope="session")
def preset_name(request):
    return request.config.getoption("--preset")
