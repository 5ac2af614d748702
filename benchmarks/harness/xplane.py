"""Reduction of a JAX profiler trace (``*.xplane.pb``) to what the metrics
read: device busy time, time per device operation, idle gaps by what the
host was doing. Read with ``jax.profiler.ProfileData`` and nothing else.

What the trace of a v5e holds (looked at by hand, PERF.md section 3): one
plane ``/device:TPU:<n>`` per chip whose line ``XLA Ops`` carries one event
per executed HLO operation (start and duration in ns; a ``while`` encloses
the operations of its body, so per-operation time is SELF time), and a plane
``/host:CPU`` whose thread lines carry ``TraceAnnotation`` spans on the same
clock: the harness's ``bench.*`` spans and the program's own (``sweep``, the
router's, around ``step`` and its phases ``step.plan`` ... ``step.land``).
An event's name is the whole HLO instruction (``%paged_attention.24 =
bf16[...] custom-call(...), custom_call_target="tpu_custom_call", ...``):
operations are keyed here by the instruction's name without its number
(``paged_attention``, ``convolution_add_fusion``, ``fusion``), and a Mosaic
(Pallas) kernel is known by its call target. The measured window is the host
span ``bench.window``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
# host spans that idle time is charged to: the harness's, and the program's
# sweep > step > step.<phase> (paddle_tpu/serving/tracing.py)
HOST_SPAN_PREFIXES = ("bench.", "sweep", "step")
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
MOSAIC_PREFIX = "mosaic:"     # op_seconds key of a Pallas kernel
_NUMBER = re.compile(r"\.\d+$")


def op_family(hlo_text: str) -> str:
    """``%convolution_add_fusion.10 = ...`` -> ``convolution_add_fusion``;
    a Mosaic custom call gets ``mosaic:`` in front of its name."""
    name = hlo_text.split(" = ", 1)[0].strip().lstrip("%")
    name = _NUMBER.sub("", name)
    return MOSAIC_PREFIX + name if MOSAIC_TARGET in hlo_text else name


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # averaged over the chips
    op_seconds: Dict[str, float]       # self time per operation name, ditto
    idle_by_span: Dict[str, float]     # idle seconds of chip 0 by host span
    n_chips: int
    n_events: int

    def seconds_of(self, *needles: str) -> float:
        """Self time of the operations whose name holds any of ``needles``."""
        return sum(s for name, s in self.op_seconds.items()
                   if any(n in name for n in needles))

    def breakdown(self, top: int = 10) -> Dict[str, List[List[object]]]:
        def first(d):
            return [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:top]]
        return {"device_ops": first(self.op_seconds),
                "idle_gaps": first(self.idle_by_span)}


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _self_times(events: List[Tuple[float, float, str]],
                lo: float, hi: float) -> Dict[str, float]:
    """Self time per name of possibly nested (start, end, name) events,
    clipped to [lo, hi]."""
    out: Dict[str, float] = {}
    stack: List[List[object]] = []   # [end, name, self]

    def close(upto: float):
        while stack and stack[-1][0] <= upto:
            end, name, self_t = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_t, 0.0)

    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        close(a)
        if stack:
            stack[-1][2] -= (min(b, stack[-1][0]) - a)
        stack.append([b, name, b - a])
    close(float("inf"))
    return out


def summarize(trace_dir_or_file: str, n_chips: int) -> Summary:
    from jax.profiler import ProfileData

    path = (trace_dir_or_file if trace_dir_or_file.endswith(".pb")
            else find_xplane(trace_dir_or_file))
    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Tuple[float, float, str]]] = {}
    host_spans: List[Tuple[float, float, str]] = []
    window: Optional[Tuple[float, float]] = None
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                families: Dict[str, str] = {}   # whole text -> family

                def family(text):
                    got = families.get(text)
                    if got is None:
                        got = families[text] = op_family(text)
                    return got

                device_ops[plane.name] = [
                    (e.start_ns, e.start_ns + e.duration_ns, family(e.name))
                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if not e.name.startswith(HOST_SPAN_PREFIXES):
                        continue
                    span = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if e.name == WINDOW_SPAN:
                        window = span[:2]
                    else:
                        host_spans.append(span)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    if not device_ops:
        raise ValueError(f"no {DEVICE_PLANE_PREFIX}* plane with a "
                         f"{OPS_LINE!r} line in {path}")
    lo, hi = window
    planes = sorted(device_ops)[:n_chips]
    busy_total = 0.0
    ops_total: Dict[str, float] = {}
    idle_by_span: Dict[str, float] = {}
    n_events = 0
    for i, name in enumerate(planes):
        events = device_ops[name]
        n_events += len(events)
        busy = _union([(max(a, lo), min(b, hi)) for a, b, _ in events
                       if min(b, hi) > max(a, lo)])
        busy_total += sum(b - a for a, b in busy)
        for op, s in _self_times(events, lo, hi).items():
            ops_total[op] = ops_total.get(op, 0.0) + s
        if i == 0:
            idle_by_span = _idle_by_span(busy, lo, hi, host_spans)
    n = len(planes)
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / n,
        op_seconds={k: v * 1e-9 / n for k, v in ops_total.items()},
        idle_by_span={k: v * 1e-9 for k, v in idle_by_span.items()},
        n_chips=n, n_events=n_events)


def _innermost(spans: List[Tuple[float, float, str]]
               ) -> List[Tuple[float, float, str]]:
    """Nested (start, end, name) spans as disjoint pieces in time order,
    each named by the innermost span that is open there."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []   # (end, name), outermost first
    t = 0.0                               # pieces are written up to here

    def close(upto: float):
        nonlocal t
        while stack and stack[-1][0] <= upto:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        close(a)
        if stack:
            if a > t:
                out.append((t, a, stack[-1][1]))
            b = min(b, stack[-1][0])   # a child ends with its parent
        t = a
        stack.append((b, name))
    close(float("inf"))
    return out


def _idle_by_span(busy, lo, hi, host_spans) -> Dict[str, float]:
    """Idle time of one chip by what the host was doing: every part of a
    gap goes to the innermost host span open during that part (``outside``
    where none is), so a gap that spans several phases is split among them
    by overlap."""
    gaps = []
    t = lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    pieces = _innermost(host_spans)
    out: Dict[str, float] = {}
    j = 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1   # ended before this gap, so before every later one
        covered = 0.0
        for k in range(j, len(pieces)):
            p0, p1, name = pieces[k]
            if p0 >= b:
                break
            part = min(b, p1) - max(a, p0)
            out[name] = out.get(name, 0.0) + part
            covered += part
        if b - a > covered:
            out["outside"] = out.get("outside", 0.0) + (b - a) - covered
    return out
