"""Test configuration: run JAX on a virtual 8-device CPU mesh.

This is the MiniCluster analog from SURVEY.md §4: the reference runs its
system tests on a 2-TM × 2-slot MiniCluster; we run ours on
``--xla_force_host_platform_device_count=8`` CPU devices so every collective
and sharding path is exercised multi-device without TPU hardware.

Must set env vars before the first jax import anywhere in the test process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
import jax

# Golden-value tests compare against numpy float64; the env var form of this
# flag is not honored by this jax build, so set it via config.
jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: repeated pytest runs skip recompiles.
from flinkml_tpu.utils import jax_cache  # noqa: E402

jax_cache.enable()

import numpy as np
import pytest


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    """``@pytest.mark.no_retrace`` wraps the test body in the analysis
    transfer/retrace guard: compile-cache misses beyond the bucket policy
    (and any declared transfer budgets) fail the test. Marker kwargs pass
    through to ``TransferRetraceGuard`` — e.g.
    ``@pytest.mark.no_retrace(allow_compiles=1)`` to budget the warmup
    compile inside the test itself."""
    marker = item.get_closest_marker("no_retrace")
    if marker is None:
        yield
        return
    from flinkml_tpu.analysis.guard import TransferRetraceGuard

    kwargs = dict(marker.kwargs)
    kwargs.setdefault("location", item.nodeid)
    guard = TransferRetraceGuard(**kwargs)
    guard.__enter__()
    outcome = yield
    # Only enforce the budget when the test body itself passed (a failing
    # test's own error is the more useful signal).
    guard.__exit__(
        None if outcome.excinfo is None else outcome.excinfo[0], None, None
    )


@pytest.fixture
def rng():
    return np.random.default_rng(2024)


@pytest.fixture(scope="session")
def mesh():
    from flinkml_tpu.parallel import DeviceMesh

    return DeviceMesh()
