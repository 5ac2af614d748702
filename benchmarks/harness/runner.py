"""One run of one cell: set-up, the measured window, the release of the
program's state, the comparison with the reference, the metrics."""
from __future__ import annotations

import json
import math
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

from . import chip, spec, xplane
from .compare import Compared, all_ok

TRACE_SECONDS_CAP = 8.0   # a traced run measures (and traces) this long


class Ctx:
    """What an entry sees of the run it is part of."""

    def __init__(self, cell: spec.Cell, seed: int, devices, t_start: float):
        self.cell, self.seed = cell, seed
        self.devices, self.t_start = devices, t_start
        self.family = spec.load_family(cell.family)

    def since_start(self) -> float:
        return time.perf_counter() - self.t_start

    def log(self, msg: str) -> None:
        print(f"[bench {self.cell.name} +{self.since_start():.1f}s] {msg}",
              file=sys.stderr, flush=True)


class Run:
    """What a metric reader sees."""

    def __init__(self, ctx: Ctx, record: Dict[str, Any], setup_s: float,
                 device: Dict[str, Any], trace: Optional[xplane.Summary]):
        self.record, self.setup_s = record, setup_s
        self.device, self.trace = device, trace
        self.config, self.family = ctx.cell.config, ctx.family
        self.chips = len(ctx.devices)
        self.peaks = (chip.peaks(device["kind"])
                      if device["platform"] == "tpu" else None)


def load_limits(cell_name: str) -> Dict[str, float]:
    """The cell's limits, benchmarks/limits/<cell>.json. A cell without the
    file, or with no limit in it, has nothing to be judged by and does not
    run."""
    path = os.path.join(spec.BENCH_DIR, "limits", cell_name + ".json")
    if not os.path.exists(path):
        raise SystemExit(f"cell {cell_name!r} has no limits file at {path}: "
                         "nothing would decide `correct`")
    with open(path) as f:
        limits = json.load(f)["limits"]
    if not limits:
        raise SystemExit(f"{path} holds no limit")
    return limits


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, need_chip: bool = True,
             limits: Optional[Dict[str, float]] = None,
             proofs: bool = False) -> Dict[str, Any]:
    """Returns the result object (the last stdout line of a run)."""
    import jax

    if need_chip:
        devices = chip.require_chips(cell.chips)
        chip.enable_compile_cache()
    else:   # the tests drive the rest of a run on whatever JAX has
        devices = jax.devices()[:cell.chips]
    ctx = Ctx(cell, seed, devices, t_start)
    limits = load_limits(cell.name) if limits is None else limits
    entry = spec.load_entry(cell.entry).build(ctx)
    entry.setup()
    window_s = min(seconds, TRACE_SECONDS_CAP) if trace else seconds
    trace_dir = os.path.join(spec.ROOT, ".bench_trace", cell.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    setup_s = ctx.since_start()
    ctx.log(f"window opens (setup_s={setup_s:.2f}, {window_s:g}s)")
    try:
        with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
            record = entry.window(window_s)
    finally:
        if trace:
            jax.profiler.stop_trace()
    ctx.log(f"window closed: {record['wall_s']:.2f}s")
    device = chip.describe(devices)
    entry.release()
    summary = None
    if trace:
        summary = xplane.summarize(trace_dir, len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    t_check = time.perf_counter()
    compared: List[Compared] = entry.verify(record, limits)
    ctx.log(f"reference comparison took {time.perf_counter() - t_check:.1f}s")
    proved = entry.proofs(limits) if proofs else {}
    if record.get("compiled_in_window"):
        compared.append(Compared("compiled_in_window",
                                 float(record["compiled_in_window"]), 0.0))
    run = Run(ctx, record, setup_s, device, summary)
    wanted = cell.per_layer if trace else cell.end_to_end
    kind = "layer_metrics" if trace else "end_to_end"
    metrics: Dict[str, Any] = {}
    for m in wanted:
        value = spec.load_reader(kind, m["name"]).read(run)
        if value is None:
            continue   # nothing to read: the metric stays out of the line
        if not math.isfinite(value):
            raise RuntimeError(f"metric {m['name']} read {value}")
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result: Dict[str, Any] = {
        "correct": all_ok(compared) and record["failed"] == 0,
        "attempted": record["attempted"], "failed": record["failed"],
        "metrics": metrics,
        "device": {k: v for k, v in device.items() if v is not None},
    }
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    if proved:   # benchmarks/proofs.py only: never part of a benchmark run
        result["proofs"] = {
            label: {"correct": all_ok(cs),
                    "compared": {c.name: c.as_json() for c in cs}}
            for label, cs in proved.items()}
        for label, cs in proved.items():
            for c in cs:
                print(f"proof {label} {c.name}: {c.value:.6g} "
                      f"(limit {c.limit})", file=sys.stderr, flush=True)
    result["compared"] = {c.name: c.as_json() for c in compared}
    for c in compared:
        print(f"compared {c.name}: {c.value:.6g} (limit {c.limit}) "
              f"{'ok' if c.ok else 'OVER'}", file=sys.stderr, flush=True)
    return result
