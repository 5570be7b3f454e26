#!/usr/bin/env python3
"""The on-chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Loads the cell, sets it up, measures for ``--seconds``, checks every output
against the plain reference and prints one JSON line: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
its per-layer metrics with ``--trace 1``), ``device`` and, last, ``checks``
(each number compared, with its limit).  Off the chip, or on a device not
in ``peaks.json``, it exits 3 and prints no result.  See ``harness.py``.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
