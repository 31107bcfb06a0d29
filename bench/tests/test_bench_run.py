"""``bench/run.py`` refuses to measure anywhere but on a TPU, and without
the program beside it."""
import json
import os
import shutil
import subprocess
import sys

from bench import harness

ROOT = harness.ROOT


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def _no_result(proc):
    for line in proc.stdout.splitlines():
        try:
            assert "metrics" not in json.loads(line)
        except ValueError:
            pass


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _run(ROOT, "--workload", "char.hbm-stream", "--seed",
                "3000000001", "--seconds", "1", "--trace", "0")
    assert proc.returncode == 3, proc.stderr
    assert "TPU" in proc.stderr
    _no_result(proc)


def test_unknown_cell_exits_nonzero():
    proc = _run(ROOT, "--workload", "no.such-cell", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert proc.returncode == 2
    _no_result(proc)


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, "--workload", "char.hbm-stream", "--seed", "7",
                "--seconds", "1", "--trace", "1")
    assert proc.returncode == 2, proc.stderr
    assert "program under test" in proc.stderr
    _no_result(proc)
