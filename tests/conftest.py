"""Test-suite bootstrap: the two-tier test split. Tests marked
``@pytest.mark.slow`` (soak, e2e, subprocess-mesh) are skipped unless
``--runslow`` (or ``RUN_SLOW=1``) is given, keeping the default tier-1 run
fast."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))

# default-on static verification (repro.analysis): every compile and every
# emitted command stream in the suite runs the verifier sandwich. Export
# REPRO_VERIFY=0 to measure the bare paths.
os.environ.setdefault("REPRO_VERIFY", "1")

def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="run tests marked slow (soak / e2e / subprocess-mesh)")


def pytest_collection_modifyitems(config, items):
    run_slow = os.environ.get("RUN_SLOW", "") not in ("", "0", "false")
    if config.getoption("--runslow") or run_slow:
        return
    skip = pytest.mark.skip(reason="slow test — use --runslow (or "
                                   "RUN_SLOW=1) to include")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
