"""Test configuration.

NOTE: no XLA_FLAGS here — smoke tests and benches must see 1 device.
Multi-device correctness runs through subprocesses (helpers.run_case), which
set the fake-device count before jax initializes.
"""

import os
import subprocess
import sys

import pytest

try:
    from hypothesis import HealthCheck, settings
except ModuleNotFoundError:
    # Bare environment: tests fall back to tests/_hypothesis_compat.py's
    # deterministic sampler; there is no profile to register.
    pass
else:
    settings.register_profile(
        "repro", deadline=None, max_examples=25,
        suppress_health_check=[HealthCheck.too_slow, HealthCheck.data_too_large])
    settings.load_profile("repro")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")


def run_case(case: str, devices: int = 8, timeout: int = 900) -> str:
    """Run one repro.testing.dist_cases case in a subprocess.

    CPU only: the child runs on fake host devices.  Never point it at a
    TPU — this process has imported JAX, and a parent that holds the chip
    leaves a child that needs it failing or hanging."""
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run(
        [sys.executable, "-m", "repro.testing.dist_cases", case,
         "--devices", str(devices)],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=ROOT)
    if r.returncode != 0 or f"CASE_OK {case}" not in r.stdout:
        raise AssertionError(
            f"dist case {case} failed:\nSTDOUT:\n{r.stdout[-3000:]}\n"
            f"STDERR:\n{r.stderr[-5000:]}")
    return r.stdout


@pytest.fixture(scope="session")
def dist():
    return run_case
