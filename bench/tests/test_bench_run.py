"""bench/run.py refuses to run without a TPU: a non-zero exit and no
result line, here on the CPU and in a directory that holds only the
benchmark's own files."""
import os
import shutil
import subprocess
import sys

from harness import ROOT


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "edge-chat-poisson",
         "--seed", str(2 ** 31 + 11), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_no_tpu_no_result():
    p = _run(ROOT, _env())
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs 1 TPU" in p.stderr


def test_unknown_workload_no_result():
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "nope",
                        "--seed", "1", "--seconds", "1"], cwd=ROOT,
                       env=_env(), capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, _env())
    assert p.returncode != 0
    assert "{" not in p.stdout
