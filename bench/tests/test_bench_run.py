"""run.py refuses to run where it cannot measure."""

import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT

ARGS = ["--workload", "granite-3-2b.sc_int.chat", "--seed", "5",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script, *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_nonzero_without_a_tpu_and_prints_no_result():
    p = _run(ROOT, os.path.join("bench", "run.py"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    """A directory holding BENCHMARK.json and bench/ alone has no system
    under test."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), os.path.join("bench", "run.py"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
