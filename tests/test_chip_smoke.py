"""``chip_smoke.py`` rehearsed on the CPU at tiny size.

The script's ``main`` refuses to run without a TPU; its phases are plain
functions, driven here through the Pallas interpreter (one device) and
on four forced host devices (the ``--chips 4`` phase).
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.realpath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_one_chip_phases_match_references_interpret():
    out = chip_smoke.run_one_chip(scale=1, seed=0, backend="jax-interpret",
                                  append_n=20, n_probes=4, rete_scale=1)
    assert out["facts"] > 2147
    assert out["routes"]["pallas"] > 0  # the interpreter runs every kernel
    assert out["routes"]["host"] == {}


def test_check_raises_on_mismatch():
    with pytest.raises(chip_smoke.SmokeFailure, match="differs"):
        chip_smoke.check(False, "answer differs")


def check_sharded_phase():
    out = chip_smoke.run_sharded(4, scale=1, seed=0, backend="jax")
    assert out[4]["checksum"] == out[1]["checksum"]
    assert out[4]["worker_devices"] == [[0], [1], [2], [3]]
    assert out[4]["exchange_device"]


def test_four_chip_phase_on_host_devices(on_devices):
    on_devices(check_sharded_phase, n_devices=4, timeout=900)


def _run_script(cwd, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "chip_smoke.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


@pytest.mark.parametrize("args", [(), ("--chips", "4")])
def test_main_refuses_cpu_without_ok_line(args):
    res = _run_script(REPO, *args)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "needs a TPU" in res.stderr


def test_script_alone_fails_without_ok_line(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    res = _run_script(tmp_path)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
