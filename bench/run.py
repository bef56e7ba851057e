#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once on the chip and print its result
as the last line of standard output.

    python3 bench/run.py --workload kron21.clients32 --seed 7 \
        --seconds 20 --trace 0

Exits non-zero, with no result, where JAX finds no accelerator, fewer
chips than the cell asks for, or a device kind with no entry under
``bench/peaks/``.
"""
import time

T_START = time.perf_counter()   # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the bench modules are imported as the package ``bench``, never by their
# bare names, which could shadow the standard library's
sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + [
    p for p in sys.path if os.path.abspath(p or ".") != HERE]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
