"""Shared fixtures. Tests run on the single CPU device (dry-runs force 512
host devices in their own process only); multi-device tests spawn
subprocesses with XLA_FLAGS set — see ``run_subprocess``."""
from __future__ import annotations

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

# Deterministic, fail-fast numerics for the whole suite.
jax.config.update("jax_default_matmul_precision", "highest")

# Initialize the backend NOW (1 CPU device) so later imports that set
# XLA_FLAGS (repro.launch.dryrun does, for its own subprocess use) cannot
# change this process's device count mid-suite.
_ = jax.devices()


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def run_subprocess(script: str, num_devices: int = 8, timeout: int = 600) -> str:
    """Run ``script`` in a fresh python with ``num_devices`` fake host devices.

    Returns stdout; raises with stderr on failure. Used by the multi-device
    integration tests (pipeline parallelism, elastic restart, shard_map)
    that cannot run in the 1-device test process.
    """
    env = dict(os.environ)
    # CPU rehearsal children: never contend with a parent for an accelerator.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={num_devices}"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr[-4000:]}"
        )
    return proc.stdout
