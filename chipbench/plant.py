#!/usr/bin/env python3
"""Run one cell as ``run.py`` does, with a fault from ``harness.faults``
planted underneath the program (one the check must catch: ``correct``
reads false).

    python3 chipbench/plant.py psum_left_out --workload <cell> \
        --seed <n> --seconds <s> --trace 0"""
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402
from harness import faults  # noqa: E402

if __name__ == "__main__":
    if len(sys.argv) < 2 or sys.argv[1] not in faults.PLANTS:
        sys.exit(f"usage: plant.py {{{','.join(faults.PLANTS)}}} "
                 "<run.py arguments>")
    with faults.PLANTS[sys.argv[1]]():
        run.main(sys.argv[2:])
