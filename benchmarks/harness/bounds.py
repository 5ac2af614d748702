"""The rule every end-to-end ``bound`` of BENCHMARK.json is set by, and the
check of the bounds against the runs they were set from: one record a cell,
``benchmarks/bounds/<cell>.json`` (two sets of same-code runs on the chip,
one seed a run, the same seeds in both sets; the commit and who measured),
and what was measured beside the cells under ``benchmarks/bounds/witness/``.
A new cell brings its own record and touches no other.

The spread of one set and one metric is the driver's: the range of the
set's values, leaving out the one run farthest from the set's median, over
the set's median. A bound is at least twice the mean of the two sets'
spreads (a check refuses a cell whose own runs spread by more than half its
bound) and at most eight times the wider of the two (beyond that it is
refused as loose), never under 1% and never over 10%, written to one half of
a percent. Where several cells report the metric, every cell has to be
admitted under the bound, and the cell that spreads most decides how loose
it may be. ``setup_s`` is the contract's 10%: a check judges its median
alone, so only the lower end applies to it.
"""
from __future__ import annotations

import json
import os
import statistics
from typing import Any, Dict, List, Sequence, Tuple

from .spec import BENCH_DIR

FLOOR, CEILING, STEP = 0.01, 0.10, 0.005
FIXED = {"setup_s": 0.10}     # the contract's; judged by its median alone
RECORDS = os.path.join(BENCH_DIR, "bounds")


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


def _read_dir(directory: str) -> Dict[str, Any]:
    if not os.path.isdir(directory):
        return {}
    out = {}
    for name in sorted(os.listdir(directory)):
        if name.endswith(".json"):
            with open(os.path.join(directory, name)) as f:
                out[name[:-len(".json")]] = json.load(f)
    return out


def load(directory: str = RECORDS) -> Dict[str, Any]:
    """{"cells": {cell: its record}, "witnesses": {name: record}}, each from
    the file of that name, and the ``directory`` they were read from."""
    return {"directory": directory, "cells": _read_dir(directory),
            "witnesses": _read_dir(os.path.join(directory, "witness"))}


def _sets(record: Dict[str, Any], metric: str) -> List[List[float]]:
    return [one["runs"][metric] for one in record["sets"]]


def _reporting(bench: Dict[str, Any], metric: Dict[str, Any]) -> List[str]:
    return metric.get("workloads", [w["name"] for w in bench["workloads"]])


def table(bench: Dict[str, Any], recorded: Dict[str, Any]) -> List[Dict]:
    """One row per end-to-end metric: the cell that spreads most, its two
    spreads, the rule's range and the bound BENCHMARK.json holds."""
    rows = []
    for m in bench["end_to_end"]:
        name = m["name"]
        per_cell = {c: _sets(recorded["cells"][c], name)
                    for c in _reporting(bench, m)}
        widest = max(per_cell, key=lambda c: sum(map(spread, per_cell[c])))
        lowest, highest = rule_range(per_cell[widest])
        if name in FIXED:
            highest = FIXED[name]
        rows.append({"metric": name, "cell": widest,
                     "spreads": [spread(s) for s in per_cell[widest]],
                     "lowest": lowest, "highest": highest,
                     "bound": m["bound"]})
    return rows


def complaints(bench: Dict[str, Any], recorded: Dict[str, Any]) -> List[str]:
    """What is wrong between BENCHMARK.json's bounds and the records, one
    line each and none where all is well: a cell with no record; a cell
    whose own two sets would not be admitted under a bound; a bound looser
    than the cell that spreads most allows. Said so that a cell added with
    its own record changes nothing for the cells that are there."""
    missing = [w["name"] for w in bench["workloads"]
               if w["name"] not in recorded["cells"]]
    if missing:     # nothing else can be said without the records
        return [f"cell {cell}: no record of runs at "
                f"{os.path.join(recorded['directory'], cell + '.json')}"
                for cell in missing]
    out: List[str] = []
    for m, row in zip(bench["end_to_end"], table(bench, recorded)):
        name, bound = m["name"], m["bound"]
        for cell in _reporting(bench, m):
            lowest, _ = rule_range(_sets(recorded["cells"][cell], name))
            if bound < lowest:
                out.append(
                    f"cell {cell}, metric {name}: its own two sets spread so "
                    f"that a bound under {lowest:.4f} would refuse it, and "
                    f"the bound is {bound}: steady the traffic (a bound moves "
                    "only in a `benchmark` PR)")
        if bound > row["highest"]:
            out.append(f"metric {name}: bound {bound} is looser than "
                       f"{row['highest']:.4f}, the most that {row['cell']}, "
                       "the cell that spreads most, allows")
        if name in FIXED and bound != FIXED[name]:
            out.append(f"metric {name}: the contract's bound is "
                       f"{FIXED[name]}, BENCHMARK.json holds {bound}")
    return out
