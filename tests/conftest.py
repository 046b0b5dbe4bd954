"""Test configuration: force JAX onto a virtual 8-device CPU platform.

Multi-chip sharding paths (opentsdb_tpu.parallel) are exercised on 8 virtual
CPU devices; the chip runs chip_smoke.py and the ``tpu``-marked tests. Must
run before any jax import, hence the env mutation at conftest import time.
"""

import os

# Override unconditionally: tests ask for the CPU by name whatever the
# ambient environment says — except under RUN_TPU_TESTS=1, which runs
# ONLY the @pytest.mark.tpu hardware tests against the real chip (one
# process per chip: nothing else may hold it meanwhile).
_TPU_RUN = bool(os.environ.get("RUN_TPU_TESTS"))
if not _TPU_RUN:
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

# Pytest plugins (jaxtyping, typeguard, ...) import jax before this file
# runs, so the env mutation alone may be too late for jax.config's cached
# default — but backends initialize lazily, so updating the config here
# (before any computation) still forces the virtual CPU mesh.
import jax  # noqa: E402

if not _TPU_RUN:
    jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _require_real_tpu():
    """Under RUN_TPU_TESTS=1, fail loudly if JAX silently resolved to
    CPU (no chip found): otherwise every parity test
    compares CPU-vs-CPU and the hardware gate passes vacuously."""
    if _TPU_RUN:
        platform = jax.devices()[0].platform
        assert platform == "tpu", (
            f"RUN_TPU_TESTS=1 but default backend is {platform!r} — "
            "no real TPU; refusing to record a vacuous hardware pass")
    yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tpu: requires a real TPU chip (run with RUN_TPU_TESTS=1; "
        "excluded from the default CPU suite)")
    config.addinivalue_line(
        "markers",
        "slow: long-running sweep (full crash matrix); excluded from "
        "tier-1 via -m 'not slow'")


def pytest_collection_modifyitems(config, items):
    if _TPU_RUN:
        # Hardware session: run ONLY the tpu-marked tests.
        skip = pytest.mark.skip(reason="CPU test (hardware-only session)")
        for item in items:
            if "tpu" not in item.keywords:
                item.add_marker(skip)
        return
    skip = pytest.mark.skip(reason="needs real TPU (set RUN_TPU_TESTS=1)")
    for item in items:
        if "tpu" in item.keywords:
            item.add_marker(skip)
