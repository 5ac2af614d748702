#!/usr/bin/env python3
"""The control and the planted faults of one cell, read beside a run.

    python3 benchmarks/proofs.py --workload <name> --seed <n> --seconds <s>

A run of the cell as benchmarks/run.py makes it, then the controls (the plain
reference computed in int8 and in fp8, put in the program's place) and the
cell's planted faults, each judged by the cell's own comparison. The result
object gains ``proofs``: per control or fault, ``correct`` and the numbers
compared. The benchmark's own runs never do this; PERF.md holds what it read.
"""
import sys

import run

if __name__ == "__main__":
    sys.exit(run.main(proofs=True))
