"""chip_smoke.py rehearsed on the CPU at tiny sizes.

The driver runs ``chip_smoke.py`` on a TPU at deployment size; these tests
run the same phases and checks (reference replay, Pallas parity in
interpret mode, device-verify parity, the served path) at shapes a CPU
finishes in seconds, and check that the script refuses to run without a
TPU, or without the rest of the repository, and prints no verdict then.
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_engine_phase(capsys):
    chip_smoke.phase_engine(3, capacity=512, voters=64, reference_sessions=36)
    line = capsys.readouterr().out
    assert "reference=identical" in line and "pallas=identical" in line
    assert "full_quorum_sessions=64" in line


def test_signed_phase(capsys):
    chip_smoke.phase_signed(4, capacity=64, voters=8, proposals=2, batch=8)
    line = capsys.readouterr().out
    assert "verdicts=identical statuses=identical" in line


def test_served_phase(capsys):
    chip_smoke.phase_served(5, capacity=64, voters=64, proposals=4)
    assert "fingerprint=identical" in capsys.readouterr().out


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_refuses_without_tpu_or_repo(tmp_path, where):
    """No verdict on the CPU, nor from a directory holding only the
    script; the exit code says so."""
    cwd = REPO
    if where == "alone":
        cwd = str(tmp_path)
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), cwd)
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
    )
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    if where == "repo":
        assert "no TPU found" in proc.stderr


def test_sharded_phase(capsys):
    """The --chips 4 path on four of conftest's virtual CPU devices."""
    chip_smoke.phase_sharded(4, capacity_per_device=128, voters=64)
    line = capsys.readouterr().out
    assert "mesh_devices=4" in line and "single_device=identical" in line
