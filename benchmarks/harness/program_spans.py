"""Readings off the program's own spans and counters: the ``sweep``, ``step``
and ``step.*`` spans that ``Router.step`` and ``ServingEngine.step`` record on
the request-trace ring (``paddle_tpu/serving/tracing.py``), and the grid
counters on each ``step`` span. The arithmetic of the eleven metrics that
read them is here once; each metric's file is one import.

The window's steps are found without a hook in the entry: after the window
nothing steps the engine again (the entry is released, the reference is not
the program), and the process-wide tracer still holds the ring, so they are
the last ``record["engine_steps"]`` ``step`` spans in it. A program that
records no spans (any commit before they existed) gives every reader
``None``, and the metric stays out of the line.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

PHASES = ("step.plan", "step.pack", "step.dispatch", "step.wait", "step.land")


class WindowSteps:
    """The ``step`` spans of the window, each with its phases' seconds by
    name, and the ``sweep`` spans that enclose them."""

    def __init__(self, steps: List[Dict[str, Any]],
                 phases: Dict[int, Dict[str, float]],
                 sweeps: List[Dict[str, Any]]):
        self.steps, self.phases, self.sweeps = steps, phases, sweeps

    def mean_phase_s(self, name: str) -> float:
        """Over ALL the window's steps: a step without the phase adds 0, so
        the phases' means sum to the mean ``step``."""
        return sum(self.phases[s["span"]].get(name, 0.0)
                   for s in self.steps) / len(self.steps)

    def sweep_s(self) -> float:
        return sum(w["arg"] for w in self.sweeps)

    def counter(self, name: str) -> int:
        return sum(s["counts"][name] for s in self.steps)


def find(events: List[Dict[str, Any]], n_steps: int) -> Optional[WindowSteps]:
    """The last ``n_steps`` ``step`` spans of ``events`` (a snapshot of the
    ring, oldest first), or None where the ring holds fewer."""
    spans = [e for e in events if "span" in e]
    steps = [e for e in spans if e["name"] == "step" and "counts" in e]
    if n_steps <= 0 or len(steps) < n_steps:
        return None
    steps = steps[-n_steps:]
    ids = {s["span"] for s in steps}
    phases: Dict[int, Dict[str, float]] = {i: {} for i in ids}
    for e in spans:
        if e["parent"] in ids and e["name"] in PHASES:
            phases[e["parent"]][e["name"]] = e["arg"]
    sweep_ids = {s["parent"] for s in steps}
    sweeps = [e for e in spans
              if e["name"] == "sweep" and e["span"] in sweep_ids]
    if len(sweeps) != len(sweep_ids):
        return None   # a step outside a router's sweep: not this harness's
    return WindowSteps(steps, phases, sweeps)


def window_steps(run) -> Optional[WindowSteps]:
    rec = run.record
    if rec.get("kind") != "serve" or not rec.get("engine_steps"):
        return None
    if not hasattr(run, "_window_steps"):   # eleven readers, one pass
        from paddle_tpu.serving import tracing

        run._window_steps = find(tracing.get_tracer().events(),
                                 int(rec["engine_steps"]))
    return run._window_steps


# ------------------------------------------------------------- the readers
def step_host_ms(run):
    """Host time a step: mean of ``sweep`` - ``step.wait``, everything in a
    router sweep but the wait for the device."""
    w = window_steps(run)
    if w is None:
        return None
    return 1e3 * (w.sweep_s() / len(w.steps) - w.mean_phase_s("step.wait"))


def _phase(name: str):
    def read(run):
        w = window_steps(run)
        return None if w is None else 1e3 * w.mean_phase_s(name)
    read.__doc__ = f"Mean ``{name}`` over the window's steps."
    return read


phase_plan_ms = _phase("step.plan")
phase_pack_ms = _phase("step.pack")
phase_dispatch_ms = _phase("step.dispatch")
phase_wait_ms = _phase("step.wait")
phase_land_ms = _phase("step.land")


def phase_sweep_ms(run):
    """The router's own: mean of ``sweep`` - its ``step`` children."""
    w = window_steps(run)
    if w is None:
        return None
    own = w.sweep_s() - sum(s["arg"] for s in w.steps)
    return 1e3 * own / len(w.steps)


def _mean_step_ms(run, want_chunk: bool):
    w = window_steps(run)
    if w is None:
        return None
    took = [s["arg"] for s in w.steps if s["counts"]["rows"] > 0
            and (s["counts"]["chunk_rows"] > 0) == want_chunk]
    return 1e3 * sum(took) / len(took) if took else None


def step_decode_only_ms(run):
    """Mean ``step`` of the steps that ran rows and no chunk row."""
    return _mean_step_ms(run, False)


def step_chunk_ms(run):
    """Mean ``step`` of the steps that carried a prompt chunk."""
    return _mean_step_ms(run, True)


def grid_fill_pct(run):
    """Real rows over the padded rows of the buckets they ran in."""
    w = window_steps(run)
    if w is None or not w.counter("bucket"):
        return None
    return 100.0 * w.counter("rows") / w.counter("bucket")


def kv_walk_amplification(run):
    """Keys the kernel's grid walked over the keys that exist to be read
    once: 1.0 when every sequence's keys are read once a step."""
    w = window_steps(run)
    if w is None or not w.counter("kv_held"):
        return None
    return w.counter("kv_walked") / w.counter("kv_held")
