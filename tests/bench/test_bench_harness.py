"""The harness refuses where it must: no TPU, or a directory holding only the
benchmark's own files."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "tweets-live", "--seed", "1", "--seconds", "1", "--trace", "0"]


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", *ARGS], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return False
        except ValueError:
            pass
    return True


def test_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for d in spec["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)


def test_every_cell_and_metric_is_a_file_found_by_name():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in spec["configs"]}
    for w in spec["workloads"]:
        cfg = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
        assert (ROOT / "bench" / "builders" / f"{cfg['builder']}.py").is_file()
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert cfg["answer_mode"] == "exact"
        assert cfg.get("ring_policy", "block") == "block"
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file(), m["name"]
