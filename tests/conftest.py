"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches
must see 1 device (dry-run sets its own 512-device flag; distributed
tests spawn subprocesses with their own flags)."""

import os
import subprocess
import sys

import pytest


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 600):
    """Run a python snippet in a subprocess with N host devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if res.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}")
    return res.stdout


@pytest.fixture
def subproc():
    return run_with_devices


def run_on_devices(func, *args, n_devices: int, timeout: int = 600):
    """Call a module-level test helper ``func(*args)`` in a subprocess
    that sees ``n_devices`` host devices (device backends place one
    shard per device, and this process has one)."""
    code = (f"import sys; sys.path.insert(0, {os.path.dirname(__file__)!r})\n"
            f"from {func.__module__} import {func.__name__} as f\n"
            f"f(*{args!r})\n")
    return run_with_devices(code, n_devices=n_devices, timeout=timeout)


@pytest.fixture
def on_devices():
    return run_on_devices
