"""The command refuses to measure anywhere but on a TPU, and a checkout
holding only the benchmark's files cannot run it."""
import json
import os
import shutil
import subprocess
import sys

from benchutil import BENCH, REPO

ARGS = ["--workload", "paper-cnn-k10-f32.lossy", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    out = _run(REPO)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for p in spec["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    assert (tmp_path / "bench" / "run.py").is_file()
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_paths_hold_the_command():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    assert spec["command"][1].startswith(spec["paths"][0] + "/")
    assert (BENCH / "run.py").is_file()
