#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line on stdout is the result object. A run that finds no TPU, or
fewer chips than the cell asks for, exits 2 and prints no result.
"""
import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
# the TPU runtime would log under a fixed /tmp path shared by both sides
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None, proofs: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.harness import runner, spec

    cell = spec.load_cell(args.workload)
    result = runner.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                             _T_START, proofs=proofs)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
