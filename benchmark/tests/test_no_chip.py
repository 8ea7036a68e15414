"""A run without a chip exits non-zero and prints no result: with the
CPU forced, and in a directory that holds only BENCHMARK.json and the
benchmark's own files (no program)."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "h264-1080p60.motion", "--seed", str(2**40 + 7),
        "--seconds", "1", "--trace", "0"]


def run_in(root: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, str(root / "benchmark" / "run.py"),
                           *ARGS], cwd=root, env=env, capture_output=True,
                          text=True, timeout=300)


def no_result(p: subprocess.CompletedProcess) -> bool:
    for line in p.stdout.splitlines():
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict) and "metrics" in doc:
            return False
    return True


def test_cpu_only_exits_non_zero_without_a_result():
    p = run_in(ROOT)
    assert p.returncode != 0
    assert no_result(p)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_non_zero_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = run_in(tmp_path)
    assert p.returncode != 0
    assert no_result(p)
