import os
import subprocess
import sys

import pytest

try:  # pin real-hypothesis runs: CI must be reproducible (the offline shim
    # in _hypothesis_shim.py derives per-test seeds and is always pinned)
    from hypothesis import settings as _hyp_settings

    _hyp_settings.register_profile("repro-ci", derandomize=True,
                                   deadline=None)
    _hyp_settings.load_profile("repro-ci")
except ImportError:
    pass

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_multidev(code: str, n_devices: int = 8, timeout: int = 300):
    """Run a python snippet in a subprocess with N fake CPU devices.

    XLA_FLAGS must NOT be set globally (smoke tests see 1 device), so
    multi-device tests run in their own process. JAX_PLATFORMS pins the
    child to the CPU: where a TPU is attached, a parent that imported jax
    holds it.
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=timeout,
    )
    if res.returncode != 0:
        raise AssertionError(
            f"multidev subprocess failed:\nSTDOUT:\n{res.stdout[-4000:]}\n"
            f"STDERR:\n{res.stderr[-4000:]}"
        )
    return res.stdout


@pytest.fixture(scope="session")
def multidev():
    return run_multidev
