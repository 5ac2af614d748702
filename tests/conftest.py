"""Test config: force an 8-virtual-device CPU platform BEFORE jax imports.

This is the TPU analogue of the reference's fake_cpu_device.h pattern
(paddle/phi/backends/custom/fake_cpu_device.h — exercising the device plug-in
path without hardware, SURVEY.md §4): distributed/sharding logic is tested on
a virtual 8-device CPU mesh; only bench.py touches the real TPU.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

REFERENCE_TREE = "/root/reference"


def pytest_configure(config):
    # belt-and-braces with pyproject.toml [tool.pytest.ini_options]: the
    # marker stays registered when tests run from a checkout that pytest
    # didn't root at the repo (e.g. pytest tests/ from another cwd)
    config.addinivalue_line(
        "markers",
        "serving: paddle_tpu.serving continuous-batching engine tests")
    config.addinivalue_line(
        "markers",
        "metrics: paddle_tpu.metrics telemetry tests (tier-1 fast lane)")
    config.addinivalue_line(
        "markers",
        "faults: paddle_tpu.faults chaos suite — injection framework + "
        "serving resilience drills (tier-1 fast lane)")
    config.addinivalue_line(
        "markers",
        "checkpoint: paddle_tpu.checkpoint crash-consistency suite — "
        "commit-protocol crash matrix + auto-resume (tier-1 fast lane)")
    config.addinivalue_line(
        "markers",
        "sentinel: paddle_tpu.faults.TrainSentinel self-healing-training "
        "suite — detectors, escalation state machine, rollback-and-skip "
        "(tier-1 fast lane)")
    config.addinivalue_line(
        "markers",
        "analysis: paddle_tpu.analysis tpulint suite — rule fixture "
        "corpus, suppression/baseline round-trips, full-repo zero-finding "
        "gate (tier-1 fast lane)")
    config.addinivalue_line(
        "markers",
        "needs_reference: API-parity gate that reads the reference "
        f"framework's checkout at {REFERENCE_TREE}; skipped where that "
        "tree is not mounted")


def pytest_collection_modifyitems(config, items):
    """A parity gate compares this package's names with the reference's
    source tree; without the tree it has nothing to compare, which is a
    skip with a reason, not a failure (and not a vacuous pass)."""
    if os.path.isdir(REFERENCE_TREE):
        return
    skip = pytest.mark.skip(
        reason=f"{REFERENCE_TREE} (the reference framework's checkout) is "
               "not mounted: nothing to compare the API with")
    for item in items:
        if item.get_closest_marker("needs_reference"):
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _seed_everything():
    import paddle_tpu

    paddle_tpu.seed(2024)
    np.random.seed(2024)
    yield
