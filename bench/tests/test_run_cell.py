"""The command itself: no accelerator, or no engine beside it, gives a
non-zero exit and no result line."""
import os
import shutil
import subprocess
import sys

from .tiny import REPO


def run_cell(root, cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run_cell.py"), "--workload",
         "taxi-1.4gb.device_agg", "--seed", str(2**31 + 3), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_accelerator_no_result(tmp_path):
    done = run_cell(REPO, tmp_path)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no accelerator" in done.stderr


def test_benchmark_alone_no_result(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    done = run_cell(tmp_path, tmp_path)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
