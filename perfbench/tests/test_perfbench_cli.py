"""Without a TPU a run exits non-zero, names what it found, and prints no
result; so does a directory that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

from perfbench import harness

ARGS = ["--workload", "phi4mini-context-closed", "--seed", "3",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "perfbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_cpu_run_fails_and_names_the_device():
    p = _run(harness.ROOT)
    assert p.returncode != 0
    assert "cpu" in p.stderr and "TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_benchmark_alone_fails(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
