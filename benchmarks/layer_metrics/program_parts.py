"""Readings off the spans where the program builds and launches a compiled
step, on its trace ring (``paddle_tpu/profiler/ring.py``): the four children
of each ``step.dispatch`` (``step.transfer``, ``step.operands``,
``step.launch``, ``step.outputs``), and set-up's ``param.init``,
``step.trace``, ``step.lower``, ``step.compile`` and ``step.load``. The
arithmetic of the eight metrics that read them is here once; each metric's
file is one import.

A program that records none of these spans (any commit before they existed)
gives every reader ``None``, and the metric stays out of the line.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmarks.harness import program_spans

DISPATCH_PARTS = ("step.transfer", "step.operands", "step.launch",
                  "step.outputs")


def _tracer():
    from paddle_tpu.serving import tracing

    return tracing.get_tracer()


# ------------------------------------------------------------ dispatch parts
def dispatch_parts_s(events: List[Dict[str, Any]],
                     steps: List[Dict[str, Any]]) -> Optional[Dict[str, float]]:
    """Seconds of each of ``DISPATCH_PARTS`` summed over the ``step.dispatch``
    spans of ``steps`` (a call that launches twice adds both launches), or
    None where none of those dispatches has a child."""
    step_ids = {s["span"] for s in steps}
    spans = [e for e in events if "span" in e]
    dispatch_ids = {e["span"] for e in spans if e["name"] == "step.dispatch"
                    and e["parent"] in step_ids}
    out = dict.fromkeys(DISPATCH_PARTS, 0.0)
    found = False
    for e in spans:
        if e["parent"] in dispatch_ids and e["name"] in out:
            out[e["name"]] += e["arg"]
            found = True
    return out if found else None


def _window_parts(run) -> Optional[Dict[str, float]]:
    if not hasattr(run, "_dispatch_parts"):   # four readers, one pass
        w = program_spans.window_steps(run)
        parts = None if w is None else dispatch_parts_s(_tracer().events(),
                                                        w.steps)
        run._dispatch_parts = (None if parts is None else
                               {k: v / len(w.steps) for k, v in parts.items()})
    return run._dispatch_parts


def _dispatch_part(name: str):
    def read(run):
        parts = _window_parts(run)
        return None if parts is None else 1e3 * parts[name]
    read.__doc__ = (f"Mean over the window's steps of their ``{name}`` "
                    "spans under ``step.dispatch``, in ms.")
    return read


dispatch_transfer_ms = _dispatch_part("step.transfer")
dispatch_operands_ms = _dispatch_part("step.operands")
dispatch_launch_ms = _dispatch_part("step.launch")
dispatch_outputs_ms = _dispatch_part("step.outputs")


# ------------------------------------------------------------- set-up parts
SETUP_SPANS = ("param.init", "step.build", "step.trace", "step.lower",
               "step.compile", "step.load")


def wrapped(events: List[Dict[str, Any]], dropped: int) -> bool:
    """Whether the ring has lost events that set-up's spans would be among:
    it counts overwritten events it has not flushed yet (``dropped``), or a
    key of a set-up span no longer starts at its seq 0 (a flight dump
    flushes the count, but the seqs stay)."""
    if dropped:
        return True
    first: Dict[Any, int] = {}
    for e in events:   # oldest first, so the first seen is the least seq
        first.setdefault(e["req_id"], e["seq"])
    return any(first[e["req_id"]] != 0 for e in events
               if "span" in e and e["name"] in SETUP_SPANS)


def setup_part_s(events: List[Dict[str, Any]], names) -> Optional[float]:
    """Seconds of the spans named ``names`` on the ring, or None where it
    holds none."""
    took = [e["arg"] for e in events if "span" in e and e["name"] in names]
    return sum(took) if took else None


def _setup_part(*names: str):
    def read(run):
        tracer = _tracer()
        events = tracer.events()
        if wrapped(events, tracer.dropped):
            return None   # set-up's spans would be partly gone
        return setup_part_s(events, names)
    read.__doc__ = (f"Seconds of the run's {' and '.join(names)} spans: "
                    "a correct run compiles nothing in its window, so they "
                    "are set-up's.")
    return read


setup_param_init_s = _setup_part("param.init")
setup_trace_s = _setup_part("step.trace")
setup_lower_s = _setup_part("step.lower")
setup_compile_s = _setup_part("step.compile", "step.load")
