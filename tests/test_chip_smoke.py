"""``chip_smoke.py`` rehearsed on the CPU: every phase at a tiny size (the
Pallas kernels interpreted), and ``main`` refusing to run without a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def test_verify_gemm_phase():
    lines = chip_smoke.verify_gemm(n=4, batch=8, seed=7)
    assert "16 PEs, 48 DSPs" in lines[0]
    assert "backend=jax platform=cpu lanes=8" in lines[1]
    assert "oracle lanes=8 ok" in lines[2]


def test_verify_traced_phase():
    lines = chip_smoke.verify_traced(batch=8, seed=7)
    assert lines[1].startswith("frontend_matmul device sim: backend=jax")
    assert "per-pass checks (backend=numpy on the host)" in lines[2]


def test_pallas_bindings_phase():
    lines = chip_smoke.pallas_bindings(interpret=True, gemm_n=4, tile=128)
    assert len(lines) == len(chip_smoke.PALLAS_KERNELS) + 1
    assert all("interpreted" in ln and "== oracle" in ln for ln in lines)


def test_phase_failure_is_reported(monkeypatch):
    gal = chip_smoke.GALLERY["frontend_matmul"]
    monkeypatch.setattr(gal, "oracle", staticmethod(lambda a, b: a @ b + 1))
    with pytest.raises(chip_smoke.SmokeError, match="oracle"):
        chip_smoke.verify_traced(batch=4, seed=7)


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _prints_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if "ok" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_main_refuses_without_tpu():
    r = _run(SCRIPT, REPO)
    assert r.returncode != 0
    assert "no TPU present" in r.stderr
    assert not _prints_result(r.stdout)


def test_script_alone_fails(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    r = _run(str(alone), str(tmp_path))
    assert r.returncode != 0
    assert not _prints_result(r.stdout)
