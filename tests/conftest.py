"""Test configuration.

Tests run on a virtual 8-device CPU mesh, mirroring the reference's
strategy of running multi-node tests in one JVM (SURVEY.md §4:
ClusteringRule runs 3 real brokers in-process). The chip is reached only
by ``chip_smoke.py`` and ``python3 -m zbench``; ``tests/test_chip_compile.py``
compiles for a described v5e without one. Importing this file forces the
CPU, so nothing that must see the chip may import ``tests/``.
"""

import os

# Must be set before jax initializes its backends. Forced (not setdefault):
# tests run on the virtual CPU mesh whatever the environment names.
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# also through the config: the environment is read when jax is imported,
# and a pytest plugin may have imported it before this file ran
jax.config.update("jax_platforms", "cpu")
# The persistent compile cache stays off under test: CPU compiles of the
# tiny test shapes take seconds, the XLA:CPU executable serializer behind
# the cache's write path has aborted the whole pytest process before, and
# a compile for the described chip (tests/test_chip_compile.py) writes
# entries no later run here can read back.
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


def pytest_configure(config):
    # tier gating: ci.sh and the tier-1 verify run `-m "not slow"`; the
    # marker must be registered or pytest treats it as unknown (warning
    # noise, and a typo'd mark silently drops a suite out of its tier)
    config.addinivalue_line(
        "markers",
        "slow: tier-2 suites (volume pins, randomized sweeps, device-engine "
        "clusters) excluded from tier-1; run with `pytest -m slow`",
    )


@pytest.fixture
def tmp_log_dir(tmp_path):
    return str(tmp_path / "log")


def make_tpu_broker(data_dir=None, clock=None, num_partitions=1):
    """A single-node Broker whose partitions run the TPU device engine
    (shared helper for the device-engine test classes)."""
    from zeebe_tpu.engine.interpreter import WorkflowRepository
    from zeebe_tpu.runtime import Broker, ControlledClock
    from zeebe_tpu.tpu import TpuPartitionEngine

    clock = clock or ControlledClock(start_ms=1_000_000)
    repo = WorkflowRepository()
    return Broker(
        num_partitions=num_partitions,
        data_dir=data_dir,
        clock=clock,
        engine_factory=lambda pid: TpuPartitionEngine(
            pid, num_partitions, repository=repo, clock=clock
        ),
    )
