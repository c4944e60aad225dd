import os
import sys

# the suite runs on the CPU by design, with Pallas kernels in interpret
# mode; chip runs go through chip_smoke.py.  Set before any jax import.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# NOTE: do NOT set xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device; multi-device tests use subprocesses.


def pytest_addoption(parser):
    parser.addoption("--runslow", action="store_true", default=False,
                     help="also run tests marked @pytest.mark.slow")


def pytest_collection_modifyitems(config, items):
    """Fast default: @pytest.mark.slow tests only run under --runslow, so
    the tier-1 suite stays well inside the CI timeout."""
    if config.getoption("--runslow"):
        return
    skip_slow = pytest.mark.skip(reason="slow test: pass --runslow to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)
