"""``bench/run.py`` measures nothing off the chip: without a TPU, or
without the program's sources, it exits non-zero and prints no
result."""

import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ARGS = ["--workload", "qwen05b-serve-chat", "--seed", "3", "--seconds",
        "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_nonzero_on_the_cpu_and_names_the_platform():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr and "'cpu'" in p.stderr
    assert '"correct"' not in p.stdout


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's own
    directories has no program to measure."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
