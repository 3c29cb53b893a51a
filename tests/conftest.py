"""Shared test fixtures.

NOTE: XLA_FLAGS / device-count forcing is deliberately NOT set here —
smoke tests and benchmarks must see the real (single) device.  Tests that
need a multi-device mesh spawn a subprocess via ``run_subprocess``.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_subprocess(code: str, devices: int = 8, timeout: int = 900):
    """Run python code in a subprocess with a forced device count."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        raise AssertionError(f"subprocess failed:\n{out.stdout}\n{out.stderr}")
    return out.stdout


@pytest.fixture(scope="session")
def rng_key():
    import jax
    return jax.random.PRNGKey(0)
