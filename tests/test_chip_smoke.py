"""``chip_smoke.py`` off the chip: it refuses every backend but a TPU, it
needs the repository beside it, and its phases run end to end at a tiny
size on the CPU (Pallas in interpret mode), so a wrong path or argument
shows here before it costs chip time."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


def _run(script: Path, cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def _says_ok(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if json.loads(line).get("ok") is True:
                return True
        except (json.JSONDecodeError, AttributeError):
            pass
    return False


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_smoke_fails_without_a_tpu_or_the_repo(where, tmp_path):
    """Off a TPU, and in a directory with nothing of the repository but the
    script, it exits nonzero and prints no ``"ok": true`` line."""
    if where == "alone":
        script = tmp_path / SMOKE.name
        shutil.copy(SMOKE, script)
        proc = _run(script, tmp_path)
    else:
        proc = _run(SMOKE, ROOT)
        assert "refusing to run on another backend" in proc.stderr
    assert proc.returncode != 0
    assert not _says_ok(proc.stdout)


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(ROOT))
    return chip_smoke


@pytest.fixture(scope="module")
def tiny_data():
    from repro import api
    return api.make_mnist_like(240, 60, seed=0)


def test_smoke_bhfl_phase_tiny(smoke, tiny_data, capsys):
    smoke.run_bhfl_phase(tiny_data, n_nodes=3, clients=2, fel_iterations=1,
                         rounds=2)
    rounds = [ln for ln in capsys.readouterr().out.splitlines()
              if ln.startswith("[bhfl] round ")]
    assert len(rounds) == 2


def test_smoke_me_kernel_phase_tiny(smoke, capsys):
    """N=12 gives a partial row block and D=1,000 a ragged last D block."""
    smoke.me_kernel_phase(nodes=(3, 12), d=1000, interpret=True)
    assert capsys.readouterr().out.count("[me] N=") == 2


def test_smoke_fel_engines_phase_tiny(smoke, tiny_data, capsys):
    smoke.fel_engines_phase(tiny_data, n_nodes=3, clients=2,
                            fel_iterations=1)
    assert "[fel] reference:" in capsys.readouterr().out
