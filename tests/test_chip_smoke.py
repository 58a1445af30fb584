"""``chip_smoke.py`` on a CPU host: every phase runs at tiny sizes with the
Pallas interpreter, and the script still refuses to report success, because
the platform is not a TPU."""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def compile_cache_restored():
    """The smoke turns on the persistent compile cache for its process;
    give the test worker its previous setting back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


def test_smoke_phases_run_tiny_and_refuse_off_tpu(tmp_path, capsys, compile_cache_restored):
    smoke = _load_smoke()
    rc = smoke.main(["--tiny", "--work-dir", str(tmp_path),
                     "--compile-cache", str(tmp_path / "jax_cache")])
    out, err = capsys.readouterr()
    assert rc != 0
    assert '"ok": true' not in out
    for phase in ("A", "A'", "B", "C"):
        assert f"[smoke] phase {phase}: epochs=" in out, out
    assert "pallas vs jnp: bitwise=True" in out
    # every check passed; the only refusal is the platform
    assert "FAILED" not in out + err, err
    assert "no TPU, no result" in err
    last = out.strip().splitlines()[-1]
    with pytest.raises(json.JSONDecodeError):
        json.loads(last)


def test_smoke_without_tiny_refuses_cpu_before_running(capsys, compile_cache_restored):
    smoke = _load_smoke()
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "[smoke] phase" not in out and '"ok"' not in out
    assert "no TPU" in err


def test_smoke_alone_in_a_directory_fails_without_result(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "cannot import" in proc.stderr
