"""A run that finds no TPU fails and prints no result, and a checkout
that holds only the benchmark's files cannot run at all."""
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ARGS = ["--workload", "paper_edge.fig5", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def run(cwd):
    return subprocess.run(
        [sys.executable, "bench/run.py", *ARGS], cwd=cwd,
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_no_tpu_exits_nonzero_without_a_result():
    p = run(ROOT)
    assert p.returncode != 0
    assert "tpu" in p.stderr
    assert '"correct"' not in p.stdout


def test_benchmark_files_alone_do_not_run(tmp_path):
    """Past the chip check, the run stops where it needs the program."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import sys; sys.path.insert(0, '.'); import bench.run as r; "
            f"r.REQUIRED_PLATFORM = 'cpu'; r.main({ARGS!r})")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                PYTHONPATH=""))
    assert p.returncode != 0
    assert "No module named 'repro'" in p.stderr
    assert '"correct"' not in p.stdout
