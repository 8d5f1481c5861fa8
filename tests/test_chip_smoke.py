"""``chip_smoke.py`` never reports success without a TPU: under
``JAX_PLATFORMS=cpu``, or copied away from the repository, it exits nonzero
and prints no ``"ok": true`` line."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

SMOKE = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_refuses_without_tpu(tmp_path, where):
    script = SMOKE
    if where == "alone":
        script = tmp_path / SMOKE.name
        shutil.copy(SMOKE, script)
    out = _run(script, tmp_path)
    assert out.returncode != 0, out.stdout
    assert '"ok": true' not in out.stdout
    assert "chip_smoke:" in out.stderr


def test_use_pallas_on_tpu_has_no_reference_fallback(monkeypatch):
    """On a TPU the kernels always run compiled: REPRO_USE_PALLAS=0 is an
    error there, not a silent switch to the jnp references."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_USE_PALLAS", raising=False)
    assert ops.use_pallas() and not ops._interpret()
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    assert ops.use_pallas()
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    with pytest.raises(RuntimeError, match="REPRO_USE_PALLAS"):
        ops.use_pallas()
    # CPU: references by default, interpret-mode kernels on request
    monkeypatch.setattr(ops, "backend", lambda: "cpu")
    assert not ops.use_pallas()
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    assert ops.use_pallas() and ops._interpret()
