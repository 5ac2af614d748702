#!/usr/bin/env python
"""Render the request trace journal as a Chrome-trace / Perfetto file.

The read-side view of ``paddle_tpu.serving.tracing`` (ISSUE 17,
docs/OBSERVABILITY.md "Request tracing & flight recorder"): each request
becomes ONE named track — every gap between consecutive journal events
is a slice labeled by the event that ENDS it, so a track reads as
"where this request's time went" (queue wait ends at req.admit, a
prefill wait ends at req.chunk, a migration hop shows as
req.export/req.adopt slices) — each engine's ``step.tokens`` events
become a counter track, and the spans (the router's ``sweep``, each
engine's ``step`` and its ``step.plan`` … ``step.land`` phases) become
"X" slices with their own start and duration on one track per engine,
nested as they ran, the ``step`` slice carrying its grid counters in
``args``. A request that hopped engines mid-decode
renders as ONE contiguous track: the tracer's fleet-global seq stream
orders events across the hop, and the exactly-once audit
(``tracing.validate_events``) runs before export — a duplicated or
missing event fails the dump, it does not render as a glitch.

The output is the SAME chrome-trace dialect the profiler writes
(``{"traceEvents": [...], "displayTimeUnit": "ms"}``, "X" slices with
microsecond ts/dur, "C" counters with ``args.value``) so a serving
trace and a profiler window load side by side in chrome://tracing or
https://ui.perfetto.dev.

Inputs: a flight-recorder dump (``--in flight-*.json``, as written by
``RequestTracer.dump_flight``) or ``--demo`` (a seeded 2-engine drill
that kills one engine mid-decode, so the exported trace shows a real
migration hop). Exit code 1 if the exactly-once audit fails.

Run: JAX_PLATFORMS=cpu python tools/trace_dump.py --demo --out t.json
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

__all__ = ["chrome_trace", "load_events", "main"]


def chrome_trace(events, pid=None):
    """Chrome-trace dict for a list of journal event dicts (the shape
    ``RequestTracer.events()`` / ``dump_flight`` emit). ``req.*``
    timelines become one named track per request; ``step.tokens``
    becomes one counter track per engine; spans (events with a ``span``
    id: ``t`` is the end, ``arg`` the seconds) become one track per key
    after the request tracks."""
    from paddle_tpu.serving import tracing

    pid = os.getpid() if pid is None else pid
    out = []
    problems = tracing.validate_events(events)
    req_events = [e for e in events
                  if e["name"] != "step.tokens" and "span" not in e]

    by_req = {}
    for e in req_events:
        by_req.setdefault(e["req_id"], []).append(e)
    for tid, (rid, tl) in enumerate(
            sorted(by_req.items(), key=lambda kv: str(kv[0])), start=2):
        tl.sort(key=lambda e: e["seq"])
        out.append({"name": "thread_name", "ph": "M", "pid": pid,
                    "tid": tid, "args": {"name": f"req {rid}"}})
        prev_t = tl[0]["t"]
        for e in tl:
            t0, dur = prev_t, e["t"] - prev_t
            prev_t = e["t"]
            out.append({
                "name": e["name"], "ph": "X", "cat": "request",
                "ts": t0 * 1e6, "dur": max(dur, 0.0) * 1e6,
                "pid": pid, "tid": tid,
                "args": {"req_id": str(rid), "seq": e["seq"],
                         "arg": e["arg"], "label": e["label"]}})
    span_tids = {}
    for e in events:
        if e["name"] == "step.tokens":
            out.append({"name": f"step.tokens/{e['req_id']}", "ph": "C",
                        "cat": "counter", "ts": e["t"] * 1e6, "pid": pid,
                        "args": {"value": e["arg"]}})
        elif "span" in e:
            key = str(e["req_id"])
            tid = span_tids.get(key)
            if tid is None:
                tid = span_tids[key] = len(by_req) + len(span_tids) + 2
                out.append({"name": "thread_name", "ph": "M", "pid": pid,
                            "tid": tid, "args": {"name": f"spans {key}"}})
            out.append({
                "name": e["name"], "ph": "X", "cat": "span",
                "ts": (e["t"] - e["arg"]) * 1e6, "dur": e["arg"] * 1e6,
                "pid": pid, "tid": tid,
                "args": {"key": key, "span": e["span"],
                         "parent": e["parent"], **e.get("counts", {})}})
    return ({"traceEvents": out, "displayTimeUnit": "ms"}, problems)


def load_events(path):
    """Journal events from ``path``: a flight-recorder dump (reads its
    ``events``) or a bare JSON list of event dicts."""
    with open(path) as f:
        payload = json.load(f)
    return payload["events"] if isinstance(payload, dict) else payload


def _demo_events():
    """Seeded 2-engine drill with a real mid-decode engine kill, so the
    exported trace exercises every track type including the migration
    hop. Returns the live journal."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import faults
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny
    from paddle_tpu.serving import Router, tracing

    old = tracing.set_tracer(tracing.RequestTracer(capacity=8192))
    try:
        tracer = tracing.get_tracer()
        paddle.seed(0)
        model = LlamaForCausalLM(llama_tiny(
            vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
            num_key_value_heads=2, max_position_embeddings=64))
        r = Router()
        r.add_model("m", model, replicas=2, page_size=4,
                    max_batch_slots=2)
        rng = np.random.RandomState(7)
        e0 = r.engine("m/0")
        for n, t, s in ((10, 0.9, 21), (9, 0.7, 22), (8, 1.1, 23)):
            e0.add_request(rng.randint(0, 128, (5,)), max_new_tokens=n,
                           temperature=t, seed=s)
        for _ in range(3):
            r.step()
        with faults.inject("router.engine_step",
                           raise_=RuntimeError("demo engine kill"),
                           times=1, seed=0):
            r.step()
        r.run()
        return tracer.events()
    finally:
        tracing.set_tracer(old)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Export the request trace journal as chrome-trace")
    ap.add_argument("--in", dest="inp", metavar="PATH",
                    help="flight-recorder dump (or bare event list) JSON")
    ap.add_argument("--demo", action="store_true",
                    help="run the seeded kill-mid-decode drill and "
                         "export its live journal")
    ap.add_argument("--out", default="trace_dump.json",
                    help="chrome-trace output path (default: %(default)s)")
    args = ap.parse_args(argv)
    if bool(args.inp) == bool(args.demo):
        ap.error("exactly one of --in / --demo required")
    events = _demo_events() if args.demo else load_events(args.inp)
    trace, problems = chrome_trace(events)
    with open(args.out, "w") as f:
        json.dump(trace, f, indent=1)
    n_tracks = sum(1 for e in trace["traceEvents"]
                   if e["ph"] == "M" and e["args"]["name"].startswith("req "))
    n_counters = len({e["name"] for e in trace["traceEvents"]
                      if e["ph"] == "C"})
    print(f"trace_dump: {len(events)} journal events -> {args.out} "
          f"({n_tracks} request tracks, {n_counters} counter tracks)")
    for p in problems:
        print(f"  EXACTLY-ONCE VIOLATION: {p}", file=sys.stderr)
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
