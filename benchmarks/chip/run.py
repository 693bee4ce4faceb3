#!/usr/bin/env python3
"""Benchmark entry point: one run of one cell on the chip.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  The last line of standard output is
the result as one JSON object; the numbers compared with the reference
and their limits close standard error.  Exits 2 without the program's
``src/`` or an unknown cell, 3 when JAX finds no TPU or too few chips,
printing no result either way.  See ``harness.py`` for what is timed.
"""
import time

T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T0))
