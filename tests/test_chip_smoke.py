"""``chip_smoke.py`` must refuse to report success anywhere but a TPU."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _run(script, cwd, **env):
    return subprocess.run([sys.executable, script], cwd=cwd,
                          env={**os.environ, **env}, capture_output=True,
                          text=True, timeout=300)


def _assert_refused(proc):
    assert proc.returncode != 0, proc.stdout
    assert '"ok": true' not in proc.stdout
    assert "chip_smoke: FAIL" in proc.stderr, proc.stderr[-2000:]


def test_chip_smoke_fails_on_cpu():
    _assert_refused(_run(SCRIPT, ROOT, JAX_PLATFORMS="cpu"))


def test_chip_smoke_fails_outside_a_checkout(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    _assert_refused(_run(str(lone), str(tmp_path), JAX_PLATFORMS="cpu"))
