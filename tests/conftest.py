"""Test environment: run the full XLA stack on a host-simulated 8-device CPU
mesh (≙ the reference's local[2] Spark sessions in TestSparkContext.scala:36,50 —
real engine, small local cluster).

The suite is a CPU suite: ``JAX_PLATFORMS=cpu`` is put in the environment
before jax is imported, so the children tests spawn (pool workers, host-group
ranks, probes, bench cells) inherit the same pin."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tests (chaos/e2e); tier-1 runs use -m 'not slow'")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session")
def eight_device_mesh():
    from transmogrifai_tpu.parallel import make_mesh
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    return make_mesh(8, model_parallel=2)


@pytest.fixture(autouse=True, scope="module")
def _memory_ladder_starts_clean():
    """The memory governor's ladder and its count of shrinks are the
    process's, and a worker runs file after file: a file of OOM drills must
    not make a later file's trains read as shrunk (``benchmark/produced.py``
    counts any train of a process that has ever shrunk as failed, and which
    files share a worker changes with every file added).  The counter is
    dropped through the registry's private table: a counter is monotonic for
    every user of a process and the registry has, rightly, no public way to
    forget one; only a test process starts over between files."""
    from transmogrifai_tpu.parallel import memory
    from transmogrifai_tpu.telemetry import REGISTRY
    memory.reset_memory_degrade()
    with REGISTRY._lock:
        REGISTRY._counters.pop("memory.shrinks_total", None)
    yield
