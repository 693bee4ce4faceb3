"""The entry point fails, printing no result, where it must."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[3]
ARGS = ["--workload", "pagerank.g500-s20", "--seed", str(2**31 + 5),
        "--seconds", "1", "--trace", "0"]


def _run(root):
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", *ARGS], cwd=root,
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))


def _bench_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(REPO / "benchmarks" / "chip",
                    root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def test_without_the_program(tmp_path):
    r = _run(_bench_only(tmp_path))
    assert r.returncode == 2 and r.stdout == ""


def test_without_a_tpu(tmp_path):
    root = _bench_only(tmp_path)
    (root / "src").symlink_to(REPO / "src")
    r = _run(root)
    assert r.returncode == 3 and r.stdout == ""
    assert "no TPU" in r.stderr


def test_unknown_cell(tmp_path):
    root = _bench_only(tmp_path)
    (root / "src").symlink_to(REPO / "src")
    r = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", "nope", "--seed", "1",
                        "--seconds", "1"], cwd=root, capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 2 and r.stdout == ""
