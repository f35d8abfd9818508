#!/usr/bin/env python3
"""The benchmark's command: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints progress and, as its last lines on standard error, every number
the check compared beside its limit; the last line of standard output is
the result object. Without an accelerator (or with fewer chips than the
cell asks for) it prints no result and exits non-zero.
"""
import sys
import time

T_START = time.perf_counter()

import pathlib  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
