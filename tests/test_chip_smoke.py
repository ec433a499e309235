"""chip_smoke.py's contract where no chip exists: the CPU rehearsal runs the
whole program (two clusters from one driver, every check that does not need
the device), and the default invocation fails without printing a result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(*flags, cwd=ROOT, script=SMOKE):
    return subprocess.run(
        [sys.executable, script, *flags], cwd=cwd, capture_output=True, text=True, timeout=600,
    )


def _last_json(out):
    lines = out.stdout.strip().splitlines()
    try:
        return json.loads(lines[-1]) if lines else None
    except ValueError:
        return None


@pytest.mark.slow
def test_cpu_rehearsal_passes_and_says_cpu():
    out = _run("--cpu")
    assert out.returncode == 0, out.stderr[-3000:]
    assert _last_json(out) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    summary = json.loads(out.stdout.strip().splitlines()[-2])
    assert all(summary["driver_checks"].values()), summary
    assert summary["stragglers_killed"] == 0


@pytest.mark.slow
def test_default_demands_the_chip():
    """JAX_PLATFORMS=cpu in the environment must not turn the default into
    a pass: the child is told to use the TPU and fails to find one."""
    out = _run()
    assert out.returncode != 0
    assert (_last_json(out) or {}).get("ok") is not True
    assert "Unable to initialize backend 'tpu'" in out.stderr


def test_script_alone_fails_without_the_program(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = _run(cwd=tmp_path, script=str(tmp_path / "chip_smoke.py"))
    assert out.returncode != 0
    assert _last_json(out) is None
