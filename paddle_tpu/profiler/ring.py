"""The process-wide trace ring: one bounded, always-on journal of events and
spans that every layer records on — the serving fleet's requests and steps
(``paddle_tpu.serving.tracing`` holds their catalog), a compiled function's
builds and calls (``jit``) and parameter creation (``nn``). It lives here,
below those layers, so that each of them can import it.

Design (docs/OBSERVABILITY.md "Request tracing & flight recorder"):

- **Bounded ring buffer.** ``RequestTracer`` preallocates ``capacity``
  mutable slots and overwrites the oldest event when full — the journal
  can never grow the heap on the step path, and the overwrite count
  surfaces as ``paddle_tpu_trace_dropped_events_total`` (flushed lazily:
  the hot path only bumps a local int).
- **Exactly-once keys.** Every event is keyed ``(req_id, seq)`` with a
  per-key monotone ``seq`` assigned by the process-wide tracer — a
  request that hops engines mid-decode (export → adopt) keeps one seq
  stream, so its timeline merges contiguous across the hop and a
  duplicated or missing event is detectable exactly like a duplicated
  stream chunk (``serving.tracing.validate_events``). A site that is not
  a request keys on one fixed name (an engine id, ``"router"``, a
  compiled function's ``__name__``, ``"param"``), so the seq table grows
  with the sites, never with the calls.
- **Injectable monotonic clock.** Defaults to ``time.perf_counter`` —
  the SAME clock domain ``loadgen.LoadDriver`` stamps ``t_submit`` with,
  which is what lets ``serving.tracing.attribute_ttft`` partition a
  measured TTFT exactly (±float error, not ±clock skew).
- **Low overhead.** Disabled tracing is ONE flag check (the metrics
  disabled-registry contract; pinned by tests/test_tracing.py). Enabled,
  ``emit`` mutates a preallocated slot in place — no metric calls, no
  locks, no allocation beyond the interned floats Python itself makes.
- **Spans.** ``begin`` / ``next`` / ``end`` record an interval into the
  same ring, in the same slot shape: ``t`` is the span's END and ``arg``
  its duration in seconds (the ``req.compile`` convention), plus a
  tracer-wide span id and the id of the span that was open when it
  began — an engine's ``step`` under the router's ``sweep``, the five
  ``step.*`` phases under their ``step``, a compiled call's spans under
  ``step.dispatch``. The same call opens a
  ``jax.profiler.TraceAnnotation`` of the same name for the span's life,
  so under ``jax.profiler.start_trace`` every program span also lies on
  the ``/host:CPU`` plane, on the device trace's clock: one call site,
  two sinks, one name. Counters that belong to a span (``COUNTERS``)
  ride on it as one tuple, counted where the work happens.
- **Flight recorder.** The ring is always armed; ``dump_flight`` writes
  the last ``window_s`` seconds of the timeline to disk as JSON. The
  Router calls it from crash containment and on the /healthz ok→degraded
  transition, so a post-mortem starts with the victim requests' full
  timelines already on disk (docs/RESILIENCE.md "Flight recorder").

Threading: ``emit`` and the spans ride the host's step path, which the
serving contract keeps single-threaded. A span recorded from another thread
(a compiled call or a parameter made off that path) lands in the ring, but
its parent is whatever span was open when it began, on any thread: parent
links assume one thread. ``dump_flight`` may fire from the
scrape thread (a /healthz transition) and reads a best-effort snapshot — a
slot mutating mid-dump yields one torn event in a post-mortem file, never a
crash or a lock on the step path.
"""
from __future__ import annotations

import json
import os
import re
import tempfile
import time
from typing import Callable, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from .. import faults, metrics

__all__ = ["COUNTERS", "RequestTracer", "Span", "get_tracer", "set_tracer"]

faults.declare_point(
    "tracing.dump", "top of RequestTracer.dump_flight, before the ring "
    "snapshot and the post-mortem file write — a raise simulates a full "
    "disk / unwritable flight dir; callers (router crash containment, "
    "/healthz transitions) must treat a failed dump as diagnostics "
    "lost, never as a serving failure")

# Counters carried by a span, in the order its ``end(counts=...)`` tuple
# gives them; ``events()`` renders them as a dict under ``"counts"``.
COUNTERS: Dict[str, Tuple[str, ...]] = {
    # rows: real rows of the token grid; bucket: its padded length T;
    # seqs: slots with a row; kv_walked: sum over real rows of (row
    # position + 1), the keys the paged kernel's grid walks this step;
    # kv_held: sum over those slots of (last row's position + 1), the
    # keys that exist to be read once — all of the step the call landed;
    # landed: tokens landed; ahead: 1 where the call launched a step while
    # the previous one was still in flight; discarded: rows thrown away
    # because their sequence had ended before they landed
    "step": ("rows", "bucket", "decode_rows", "chunk_rows", "draft_rows",
             "seqs", "kv_walked", "kv_held", "landed", "ahead",
             "discarded"),
    # fresh: 1 where the build paid a trace and an XLA compile, 0 where a
    # cache held the program (paddle_tpu_jit_compiles_total's source)
    "step.build": ("fresh",),
}

_REASON_SAFE_RE = re.compile(r"[^a-zA-Z0-9_.-]+")


class Span:
    """One open span: what ``begin`` hands out and ``end`` closes."""

    __slots__ = ("name", "key", "sid", "outer", "t0", "ann")


class RequestTracer:
    """Always-on bounded event journal keyed ``(req_id, seq)``.

    One process-wide instance (:func:`get_tracer`) serves the whole
    process: every engine, the router and the compiled functions record
    into the same ring, which is what makes a migrated request's timeline
    contiguous — its seq counter lives here, not on any engine.
    """

    def __init__(self, capacity: int = 65536,
                 clock: Callable[[], float] = time.perf_counter,
                 enabled: bool = True,
                 flight_dir: Optional[str] = None,
                 window_s: float = 30.0):
        cap = max(int(capacity), 16)
        self._cap = cap
        # preallocated mutable slots [t, req_id, seq, name, arg, label,
        # span id, parent span id, counts] — written in place, so a full
        # ring never grows. A point event's span id is 0.
        self._ring: List[list] = [[0.0, None, 0, "", 0.0, "", 0, 0, None]
                                  for _ in range(cap)]
        self._sid = 0           # last span id handed out
        self._open: Optional[Span] = None   # innermost open span
        self._head = 0          # next slot to write
        self._count = 0         # filled slots (== cap once wrapped)
        self._seq: Dict[object, int] = {}
        self._dropped = 0       # local; flushed lazily to the counter
        self._dumps = 0
        self.enabled = bool(enabled)
        self._clock = clock
        self.window_s = float(window_s)
        self.flight_dir = flight_dir

    # ------------------------------------------------------------- hot path
    def emit(self, name: str, req_id, arg: float = 0.0, label: str = "",
             t: Optional[float] = None) -> None:
        """Journal one event. Disabled = this flag check; enabled = a
        dict get/set (the per-request seq) plus in-place slot writes.
        Never raises, never locks, never touches a metric."""
        if not self.enabled:
            return
        if t is None:
            t = self._clock()
        self._write(t, req_id, name, arg, label, 0, 0, None)

    def _write(self, t, req_id, name, arg, label, sid, parent,
               counts) -> None:
        seq = self._seq.get(req_id, 0)
        self._seq[req_id] = seq + 1
        i = self._head
        if self._count < self._cap:
            self._count += 1
        else:
            self._dropped += 1          # overwrote the oldest event
        slot = self._ring[i]
        slot[0] = t
        slot[1] = req_id
        slot[2] = seq
        slot[3] = name
        slot[4] = arg
        slot[5] = label
        slot[6] = sid
        slot[7] = parent
        slot[8] = counts
        self._head = 0 if i + 1 == self._cap else i + 1

    # ---------------------------------------------------------------- spans
    def begin(self, name: str, key) -> Optional[Span]:
        """Open a span keyed ``key`` (an engine_id, "router", a req_id, a
        compiled function's name) under whatever span is open now, and
        enter a ``TraceAnnotation`` of the same name. Disabled = this flag
        check (returns None, which ``next`` and ``end`` pass through).
        Spans close in the order they nest: the step path is one
        thread."""
        if not self.enabled:
            return None
        sp = Span()
        sp.name, sp.key = name, key
        self._sid = sp.sid = self._sid + 1
        sp.outer = self._open
        self._open = sp
        sp.ann = TraceAnnotation(name)   # the annotation starts here
        sp.t0 = self._clock()
        return sp

    def _close(self, sp: Span, counts) -> float:
        """One ring slot for ``sp``: ``t`` = now, ``arg`` = its seconds,
        its id and its parent's (0: none). Returns ``t``."""
        sp.ann.__exit__(None, None, None)
        t = self._clock()
        outer = sp.outer
        self._write(t, sp.key, sp.name, t - sp.t0, "", sp.sid,
                    outer.sid if outer is not None else 0, counts)
        return t

    def next(self, name: str, sp: Optional[Span]) -> Optional[Span]:
        """Close ``sp`` and open its sibling ``name`` (same key, same
        parent) on ONE clock read, so successive phases tile their
        parent with no gap between them."""
        if sp is None:
            return None
        sp.t0 = self._close(sp, None)
        self._sid = sp.sid = self._sid + 1
        sp.name = name
        sp.ann = TraceAnnotation(name)
        return sp

    def end(self, sp: Optional[Span],
            counts: Optional[Tuple[int, ...]] = None) -> None:
        """Close ``sp``, with ``counts`` (a tuple in ``COUNTERS[name]``
        order) where the span carries counters."""
        if sp is None:
            return
        self._close(sp, counts)
        self._open = sp.outer

    # ------------------------------------------------------------ snapshots
    def events(self) -> List[dict]:
        """Chronological snapshot of the ring as event dicts — the read
        side (attribution, dumps, trace_dump) allocates; the write side
        never does."""
        if self._count < self._cap:
            raw = self._ring[:self._count]
        else:
            raw = self._ring[self._head:] + self._ring[:self._head]
        out = []
        for s in raw:
            e = {"t": s[0], "req_id": s[1], "seq": s[2], "name": s[3],
                 "arg": s[4], "label": s[5]}
            if s[6]:   # a span: t is its end, arg its seconds
                e["span"], e["parent"] = s[6], s[7]
                if s[8] is not None:
                    e["counts"] = dict(zip(COUNTERS.get(s[3], ()), s[8]))
            out.append(e)
        return out

    def events_for(self, req_id) -> List[dict]:
        """This request's timeline in seq order — contiguous across any
        number of migration hops (one global seq stream per req_id)."""
        out = [e for e in self.events() if e["req_id"] == req_id]
        out.sort(key=lambda e: e["seq"])
        return out

    @property
    def dropped(self) -> int:
        """Events overwritten before any export (local, pre-flush)."""
        return self._dropped

    def reset(self) -> None:
        """Forget everything (benchmark isolation). The ring stays
        allocated; seq counters restart at 0 for every req_id."""
        self._head = 0
        self._count = 0
        self._seq.clear()
        self._dropped = 0
        self._open = None

    # -------------------------------------------------------------- metrics
    def flush_metrics(self) -> None:
        """Move the locally-accumulated drop count into the registry —
        called from dump/score/export paths, NEVER per event, so the
        step path stays metric-free."""
        reg = metrics.get_registry()
        dropped = reg.counter(
            "paddle_tpu_trace_dropped_events_total",
            "Trace ring events overwritten before any export read them")
        if self._dropped:
            dropped.inc(self._dropped)
            self._dropped = 0

    # ------------------------------------------------------ flight recorder
    def dump_flight(self, reason: str, path: Optional[str] = None,
                    window_s: Optional[float] = None,
                    now: Optional[float] = None) -> str:
        """Write the last ``window_s`` seconds of fleet timeline to disk
        as JSON (``events`` chronological + ``requests`` grouped per
        req_id in seq order) and return the file path. Callers on the
        serving path guard this — a failed dump loses diagnostics, not
        requests (the armed ``tracing.dump`` fault proves it)."""
        faults.point("tracing.dump")
        if now is None:
            now = self._clock()
        win = self.window_s if window_s is None else float(window_s)
        evs = [e for e in self.events() if e["t"] >= now - win]
        requests: Dict[str, List[dict]] = {}
        for e in evs:
            requests.setdefault(str(e["req_id"]), []).append(e)
        for timeline in requests.values():
            timeline.sort(key=lambda e: e["seq"])
        payload = {"reason": str(reason), "t_dump": now, "window_s": win,
                   "dropped_events": self._dropped,
                   "events": evs, "requests": requests}
        if path is None:
            d = (self.flight_dir
                 or os.environ.get("PADDLE_TPU_FLIGHT_DIR")
                 or os.path.join(tempfile.gettempdir(),
                                 "paddle_tpu_flight"))
            os.makedirs(d, exist_ok=True)
            self._dumps += 1
            safe = _REASON_SAFE_RE.sub("-", str(reason)) or "dump"
            path = os.path.join(
                d, f"flight-{os.getpid()}-{self._dumps:03d}-{safe}.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump(payload, f, indent=1)
        reg = metrics.get_registry()
        reg.counter("paddle_tpu_trace_recorder_dumps_total",
                    "Flight-recorder dumps by trigger",
                    labels=("reason",)).labels(reason=str(reason)).inc()
        self.flush_metrics()
        return path


# --------------------------------------------------------- default tracer
_default_tracer: Optional[RequestTracer] = None


def get_tracer() -> RequestTracer:
    """The process-wide tracer every layer shares — created on first use
    from the env knobs (docs/SERVING.md "Tracing knobs"):
    ``PADDLE_TPU_TRACE=0`` disables, ``PADDLE_TPU_TRACE_CAPACITY`` sizes
    the ring, ``PADDLE_TPU_FLIGHT_DIR`` / ``PADDLE_TPU_FLIGHT_WINDOW_S``
    steer the flight recorder."""
    global _default_tracer
    if _default_tracer is None:
        _default_tracer = RequestTracer(
            capacity=int(os.environ.get("PADDLE_TPU_TRACE_CAPACITY",
                                        "65536") or 65536),
            enabled=os.environ.get("PADDLE_TPU_TRACE", "1") != "0",
            flight_dir=os.environ.get("PADDLE_TPU_FLIGHT_DIR"),
            window_s=float(os.environ.get("PADDLE_TPU_FLIGHT_WINDOW_S",
                                          "30") or 30.0))
    return _default_tracer


def set_tracer(tracer: Optional[RequestTracer]) -> \
        Optional[RequestTracer]:
    """Swap the process-wide tracer (tests inject a virtual clock or a
    tiny ring); returns the previous one. ``None`` resets to lazy env
    construction."""
    global _default_tracer
    old = _default_tracer
    _default_tracer = tracer
    return old
