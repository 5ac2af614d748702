"""Per-request tracing: the serving fleet's catalog of events and spans on
the process-wide trace ring, and the read side that turns a request's
timeline into where its time went.

The request-scoped third leg of observability: metrics aggregate,
the profiler samples inside RECORD windows, and the ring journals the
LIFECYCLE of every individual request — always on, so when
BENCH_LOAD.json says interactive TTFT attainment is 0.51 the trace can
say *where* each missed request's milliseconds went (queue wait vs.
chunked prefill vs. compile vs. migration hop) instead of shrugging at
an aggregate histogram.

The ring itself — ``RequestTracer``, its spans and counters, the flight
recorder, ``get_tracer`` / ``set_tracer`` — lives in
``paddle_tpu.profiler.ring``, below the layers that record on it (``jit``,
``nn``), and is re-exported here: there is one ring per process, whichever
module a caller names. This module keeps what is the fleet's: the event
catalog (``EVENTS``), ``validate_events`` and ``attribute_ttft``
(docs/OBSERVABILITY.md "Request tracing & flight recorder").
"""
from __future__ import annotations

from typing import Dict, List

from ..profiler.ring import (COUNTERS, RequestTracer, Span, get_tracer,
                             set_tracer)

__all__ = [
    "COUNTERS", "EVENTS", "RequestTracer", "Span", "TTFT_BUCKETS",
    "attribute_ttft", "get_tracer", "set_tracer", "validate_events",
]

# The event-name catalog: every literal ``tracer.emit("<name>", ...)``
# site in the package uses one of these, and docs/OBSERVABILITY.md
# tables them — tpulint TPL010 pins both directions. ``req.*`` events
# key on the request id; ``step`` / ``step.*`` are engine-scoped spans
# (``begin``/``next``; req_id: the engine_id string), as is the router's
# ``sweep`` (req_id: "router"). The spans that ``jit`` and ``nn`` record
# on the same ring (a compiled call's and build's, ``param.init``) are
# cataloged beside these in docs/OBSERVABILITY.md.
EVENTS: Dict[str, str] = {
    "req.enqueue": "request entered an engine queue (arg: prompt tokens)",
    "req.dispatch": "router placed the request (label: engine_id)",
    "req.admit": "parked in a slot (arg: prefix-matched tokens; "
                 "label: engine_id)",
    "req.prefix_hit": "radix prefix-cache hit at admission (arg: "
                      "matched tokens; only emitted when > 0)",
    "req.chunk_planned": "plan_chunks granted this slot a prompt chunk "
                         "(arg: chunk tokens)",
    "req.drafts": "plan_drafts granted speculative draft rows, post "
                  "grammar pre-filter (arg: draft tokens)",
    "req.compile": "a fresh token-grid bucket compiled under this "
                   "request (arg: build+step seconds)",
    "req.chunk": "prompt chunk landed (arg: chunk tokens)",
    "req.spec_accept": "draft burst verified (arg: accepted drafts)",
    "req.spec_reject": "draft burst rolled back via pool.truncate "
                       "(arg: rejected drafts)",
    "req.grammar_mask": "constrained token landed, DFA advanced "
                        "(arg: new FSM state)",
    "req.park": "stream parked on the host KV tier (arg: pages "
                "offloaded)",
    "req.unpark": "parked stream's pages restored to HBM (arg: pages "
                  "prefetched)",
    "req.token": "stream chunk emitted (arg: stream seq)",
    "req.retire": "terminal (label: finish_reason)",
    "req.export": "in-flight journal exported off a dying engine "
                  "(arg: journal length; label: engine_id)",
    "req.adopt": "journal adopted by a sibling engine (arg: journal "
                 "length; label: engine_id)",
    "req.requeue": "waiting request moved to a sibling (label: target "
                   "engine_id)",
    "req.recover": "request re-admitted from the WAL after a process "
                   "restart (arg: journaled tokens; label: adoptive "
                   "engine_id)",
    "req.migrate": "in-flight request migrated to a sibling (label: "
                   "target engine_id)",
    "req.shed": "refused at admission by the overload controller "
                "(arg: predicted wait s; label: cause)",
    "req.preempt": "batch-tier decode slot journaled and requeued by "
                   "the brownout ladder (arg: journal length; label: "
                   "engine_id)",
    "req.expire": "deadline lapsed while still queued — retired "
                  "\"expired\", pages never allocated (label: "
                  "engine_id)",
    "brownout.level": "brownout ladder transition (req_id: model_id; "
                      "arg: new level; label: level name)",
    # spans: t = end, arg = seconds, span/parent ids (see ``begin``)
    "sweep": "span: one Router.step() (req_id: \"router\"); its engine "
             "steps are its children, the rest is the router's own",
    "step": "span: one ServingEngine.step() (req_id: engine_id; parent: "
            "the sweep; counts: COUNTERS[\"step\"]); tiled by step.*",
    "step.plan": "span: deadline sweep, brownout/park hooks, admission, "
                 "plan_chunks, drafting, pool reservations",
    "step.pack": "span: the numpy token grid, sample rows, FSM states",
    "step.dispatch": "span: the grids' transfer and the call of the step "
                     "program until it returns, tiled by step.transfer, "
                     "step.operands, step.launch and step.outputs; its "
                     "self time is the engine's own (compile check, "
                     "pool.set_step_flat, int8 clip count, slot "
                     "bookkeeping)",
    "step.transfer": "span (parent: step.dispatch): the grids' one "
                     "device_put, their Tensor wraps, the first-use "
                     "zeros of prev",
    "step.wait": "span: blocked on the read-back of the step this call "
                 "lands: launched by the previous call where the engine "
                 "runs one step ahead, so the device runs it and then "
                 "the one just launched",
    "step.land": "span: acceptance, token landing, stream callbacks, "
                 "prefix-cache insert, retirement, stats and gauges",
}

# TTFT attribution buckets (docs/OBSERVABILITY.md "TTFT attribution"):
# per-request bucket values always sum EXACTLY to the measured TTFT —
# the residual (clock tails, submit overhead, un-journaled gaps from a
# wrapped ring) is pinned into host_overhead rather than dropped.
TTFT_BUCKETS = ("queue", "compile", "cold_prefill", "warm_prefill",
                "decode", "migration", "host_overhead")

_MIGRATION_EVENTS = frozenset(
    ("req.export", "req.adopt", "req.requeue", "req.migrate"))
_DECODE_EVENTS = frozenset(("req.token", "req.grammar_mask",
                            "req.spec_accept", "req.spec_reject"))
_QUEUE_EVENTS = frozenset(("req.admit", "req.prefix_hit"))



def validate_events(events: List[dict]) -> List[str]:
    """Exactly-once audit of one request's timeline: every ``(req_id,
    seq)`` unique, seqs contiguous from the smallest captured one (a
    wrapped ring legitimately loses the OLDEST prefix, never punches a
    hole). Returns human-readable problems; [] is the pass."""
    problems: List[str] = []
    by_req: Dict[object, List[int]] = {}
    for e in events:
        by_req.setdefault(e["req_id"], []).append(int(e["seq"]))
    for rid, seqs in sorted(by_req.items(), key=lambda kv: str(kv[0])):
        seqs.sort()
        dupes = sorted({s for i, s in enumerate(seqs)
                        if i and seqs[i - 1] == s})
        if dupes:
            problems.append(f"req {rid}: duplicate seq(s) {dupes}")
        want = list(range(seqs[0], seqs[0] + len(seqs)))
        if not dupes and seqs != want:
            missing = sorted(set(want) - set(seqs))[:8]
            problems.append(f"req {rid}: missing seq(s) {missing}")
    return problems


def attribute_ttft(events: List[dict], t_submit: float,
                   t_first: float) -> Dict[str, float]:
    """Decompose one request's measured TTFT into :data:`TTFT_BUCKETS`.

    Partition ``(t_submit, t_first]`` at the request's trace events and
    charge each gap to the bucket of the event that ENDS it: the wait
    that ended in admission was queue time, the wait that ended in a
    chunk landing was prefill (warm when a prefix-cache hit covered part
    of the prompt, cold otherwise), the wait that ended in a fresh-
    bucket compile was compile, a migration-hop event charges its gap to
    migration. Whatever the events don't cover — submit overhead, the
    tail after the last event, timelines truncated by ring wrap — lands
    in ``host_overhead`` as the exact residual, so::

        sum(attribute_ttft(...).values()) == t_first - t_submit

    holds to float precision (the BENCH_LOAD ±1 ms acceptance bound is
    slack, not a fudge factor).
    """
    out = {b: 0.0 for b in TTFT_BUCKETS}
    measured = t_first - t_submit
    window = [e for e in events if t_submit < e["t"] <= t_first]
    window.sort(key=lambda e: e["seq"])
    warm = any(e["name"] == "req.prefix_hit" for e in window)
    prev = t_submit
    classified = 0.0
    for e in window:
        gap = e["t"] - prev
        prev = e["t"]
        if gap <= 0.0:
            continue
        name = e["name"]
        if name in _QUEUE_EVENTS:
            bucket = "queue"
        elif name == "req.compile":
            bucket = "compile"
        elif name == "req.chunk":
            bucket = "warm_prefill" if warm else "cold_prefill"
        elif name in _DECODE_EVENTS:
            bucket = "decode"
        elif name in _MIGRATION_EVENTS:
            bucket = "migration"
        else:
            # enqueue/dispatch/plan decisions: host bookkeeping
            bucket = "host_overhead"
        out[bucket] += gap
        classified += gap
    out["host_overhead"] += measured - classified
    return out

