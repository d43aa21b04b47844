"""Test bootstrap: force CPU backend with 8 virtual devices (mirrors the
reference's gloo-on-CPU multi-process CI substitution,
test_parallel_dygraph_dataparallel.py:67 — see SURVEY.md §4.2).  The
platform is pinned in the config as well as asked for with
``JAX_PLATFORMS=cpu``, before any backend is initialized: the tests never
take a TPU."""

import os
import warnings

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

warnings.filterwarnings("ignore", message=".*dtype int64 requested.*")

# exact f32 matmuls for numpy-oracle comparisons (the perf path uses bf16 anyway)
jax.config.update("jax_default_matmul_precision", "highest")


def pytest_configure(config):
    # tier-1 runs with -m 'not slow'; register the marker so slow-marked
    # tests (e.g. subprocess CLI smoke) deselect without unknown-mark noise
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 suite (-m 'not slow')")


@pytest.fixture(scope="session")
def eight_devices():
    assert jax.device_count() == 8
    return jax.devices()
