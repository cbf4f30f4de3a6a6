import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

# REPRO_HOST_DEVICES=<n> forces N XLA host devices for the whole run (CI's
# tp leg runs the distributed/serving/TP subset on 8). Must land in
# XLA_FLAGS here, before anything initializes a jax backend; tests gate on
# len(jax.devices()) and skip when the flag isn't set.
_HOST_DEVS = os.environ.get("REPRO_HOST_DEVICES")
if _HOST_DEVS and "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={int(_HOST_DEVS)}"
    ).strip()

# REPRO_KERNELS=<mode> pins the default-context kernel mode for the whole
# test run (CI's pallas-interpret leg re-runs the kernel/serving subset
# with the interpret-mode Pallas kernels instead of the XLA references).
_KERNELS_ENV = os.environ.get("REPRO_KERNELS")
if _KERNELS_ENV:
    import dataclasses

    from repro.core import context as _ctx
    if _KERNELS_ENV not in _ctx.KERNEL_MODES:
        raise SystemExit(f"REPRO_KERNELS={_KERNELS_ENV!r} is not a kernel "
                         f"mode; one of {_ctx.KERNEL_MODES}")
    _ctx.set_default_context(dataclasses.replace(
        _ctx.get_default_context(), kernels=_KERNELS_ENV))


def run_in_subprocess(code: str, devices: int = 1, timeout: int = 300) -> str:
    """Run python code in a fresh process with N host devices.

    Multi-device tests must not pollute this process's jax device count
    (smoke tests and benches must keep seeing 1 device).
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    if devices > 1:
        # drop any inherited count (e.g. the tp CI leg's REPRO_HOST_DEVICES
        # wiring above) so the subprocess sees exactly `devices`
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       env.get("XLA_FLAGS", ""))
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={devices}"
        ).strip()
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed\nSTDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}")
    return proc.stdout


@pytest.fixture
def subproc():
    return run_in_subprocess


@pytest.fixture(autouse=True)
def _fresh_registry():
    """Each test sees an empty global parameter registry."""
    import repro.core as nn
    nn.clear_parameters()
    yield
    nn.clear_parameters()


@pytest.fixture(autouse=True)
def _restore_default_context():
    """A test that sets the process-wide default context (the benchmark
    harness sets ``pure_bf16``) leaves the next test, which may be another
    file's in the same pytest-xdist worker, the context it found."""
    from repro.core import context as ctx
    saved = ctx.get_default_context()
    yield
    ctx.set_default_context(saved)
