"""``bench/run.py`` refuses to run where it cannot measure: without a
TPU, and in a checkout that holds only the benchmark's files."""

import os
import shutil
import subprocess
import sys

from bench import harness

ROOT = harness.ROOT


def _run(cwd, extra_env=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(extra_env or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "pixelfly-chat", "--seed", str(2**33 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def _no_result(out: str) -> bool:
    return not any(line.lstrip().startswith("{") for line in out.splitlines())


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in harness.load_benchmark()["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path, ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert _no_result(p.stdout)
