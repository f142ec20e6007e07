"""The command exits non-zero and prints no result where it cannot run:
with no TPU, and in a directory that holds only the benchmark's files."""
import json
import os
import shutil
import subprocess
import sys

from bench.tests.fixture import BENCH, ROOT


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sf1-adhoc-c4",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _no_result(p):
    for line in p.stdout.splitlines():
        try:
            assert not isinstance(json.loads(line), dict)
        except ValueError:
            pass


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = _run(ROOT, env)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    _no_result(p)


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _run(str(tmp_path), env)
    assert p.returncode != 0
    _no_result(p)
