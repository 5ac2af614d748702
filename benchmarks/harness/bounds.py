"""The rule every end-to-end ``bound`` of BENCHMARK.json is set by, and the
check of the bounds against the runs they were set from
(``benchmarks/bounds.json``: per cell two sets of same-code runs on the chip,
one seed a run, the same seeds in both sets).

The spread of one set and one metric is the driver's: the range of the
set's values, leaving out the one run farthest from the set's median, over
the set's median. A bound is at least twice the mean of the two sets'
spreads (a check refuses a cell whose own runs spread by more than half its
bound) and at most eight times the wider of the two (beyond that it is
refused as loose), never under 1% and never over 10%, written to one half of
a percent. Where several cells report the metric, the cell that spreads most
decides. ``setup_s`` is the contract's 10%: a check judges its median alone,
so only the lower end applies to it.
"""
from __future__ import annotations

import json
import os
import statistics
from typing import Any, Dict, List, Sequence, Tuple

from .spec import BENCH_DIR

FLOOR, CEILING, STEP = 0.01, 0.10, 0.005
FIXED = {"setup_s": 0.10}     # the contract's; judged by its median alone
RECORD = os.path.join(BENCH_DIR, "bounds.json")


def spread(values: Sequence[float]) -> float:
    """Range over median of one set, without the run farthest from the
    median (it lies at one end, so the range narrows or stays)."""
    vals = sorted(values)
    med = statistics.median(vals)
    if len(vals) > 2:
        vals = vals[1:] if med - vals[0] > vals[-1] - med else vals[:-1]
    return (vals[-1] - vals[0]) / med


def rule_range(sets: Sequence[Sequence[float]]) -> Tuple[float, float]:
    """(lowest, highest) bound the rule allows for one metric in one cell."""
    spreads = [spread(s) for s in sets]
    mean, wider = sum(spreads) / len(spreads), max(spreads)
    lowest = min(max(FLOOR, 2.0 * mean), CEILING)
    highest = min(max(FLOOR, 8.0 * wider), CEILING)
    return lowest, max(lowest, highest)


def load(path: str = RECORD) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def table(bench: Dict[str, Any], recorded: Dict[str, Any]) -> List[Dict]:
    """One row per end-to-end metric: the cell that spreads most, its two
    spreads, the rule's range and the bound BENCHMARK.json holds."""
    cells = [w["name"] for w in bench["workloads"]]
    rows = []
    for m in bench["end_to_end"]:
        name = m["name"]
        per_cell = {c: [s["runs"][name] for s in recorded["cells"][c]["sets"]]
                    for c in m.get("workloads", cells)}
        widest = max(per_cell, key=lambda c: sum(map(spread, per_cell[c])))
        lowest, highest = rule_range(per_cell[widest])
        if name in FIXED:
            highest = FIXED[name]
        rows.append({"metric": name, "cell": widest,
                     "spreads": [spread(s) for s in per_cell[widest]],
                     "lowest": lowest, "highest": highest,
                     "bound": m["bound"]})
    return rows

