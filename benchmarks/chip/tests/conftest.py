"""CPU tests of the benchmark at tiny sizes: JAX on the CPU, the
benchmark's modules and the program's ``src/`` importable."""
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))


@pytest.fixture
def bench_root(tmp_path):
    """A copy of the benchmark's data and readers with the tiny test
    configurations and cells added to it."""
    root = tmp_path / "chip"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    for kind in ("configs", "workloads"):
        for p in (HERE / "data" / kind).glob("*.json"):
            shutil.copy(p, root / kind / p.name)
    return root
