#!/usr/bin/env python
"""Chaos drill for the serving stack: run the demo engine under a seeded
fault schedule and print a pass/fail resilience report.

The operational twin of tests/test_faults.py + tests/test_router.py
(docs/RESILIENCE.md): scenarios 1-6 arm ``paddle_tpu.faults`` injections
against a tiny llama engine — NaN quarantine, page-pool exhaustion,
compile-failure retry, deadline expiry + cancellation, queue
backpressure, watchdog trip + ``/healthz`` — and scenarios 7-10 drill the
ROUTER control plane: a NaN-poisoned + degraded engine fails its waiting
work over to a sibling exactly once (no duplicates, no drops), a rolling
``reload()`` across live traffic completes every request and lands every
engine on the new checkpoint's weights with the decode program still
compiled exactly once per engine, least-loaded dispatch beats blind
round-robin on p95 queue wait under skewed load, and a seeded
kill-engine-mid-decode drill (scenario 10): the busiest engine dies at a
scheduled step under sampled streaming traffic, ``router.step()``
contains the crash, and every in-flight request MIGRATES by token
journal — final streams bit-identical to an uninterrupted run, zero
duplicated or missing stream chunks. Scenario 11 re-runs the kill drill
under PREFIX-HEAVY traffic: migrated requests must re-prefill through the
adoptive sibling's radix prefix cache (``prefill_tokens_saved_total``
rises there), still bit-identical and exactly-once. Scenario 12 kills
the busiest engine BETWEEN PROMPT CHUNKS of a long request (ISSUE 11):
chunked-prefill progress is only a cache length, so the mid-prefill
request migrates with an empty journal, resumes from its chunk boundary
through the sibling's prefix cache, and streams bit-identically from
seq 0 — chunks exactly-once. Scenario 13 thread-fuzzes the control
plane under ``faults.LockSanitizer``: a driver thread (submit / step /
rolling reload), a /metrics+/healthz scraper and a health()/states()
prober race through 200 barrier-synced, seed-jittered iterations with
the router / registry / probe-cache / watchdog locks instrumented —
zero lock-order or reentrancy violations allowed, fleet must end
consistent. Scenario 14 re-runs the kill drill under SPECULATIVE
decoding (ISSUE 14): both replicas draft with spec_k=3 — one tenant
bursting at 100% acceptance, one fed always-rejected garbage — the
busiest engine dies between bursts, and every migrated journal must
carry only committed tokens (never an unaccepted draft), with final
streams bit-identical to a spec-off lone engine and chunks
exactly-once. Scenario 15 replays a seeded Poisson-burst loadgen trace
(ISSUE 15) against a 1-engine fleet with the queue-depth autoscaler
attached: the burst must scale the fleet up (new engines materialize
their pinned step shape from the persistent compile cache with ZERO
fresh compiles) and the post-burst cold signal must drain-then-remove
back to exactly 1 engine — every trace request completing or retiring
``"unavailable"`` exactly-once, no leaked pages or move-once marks.
Scenario 16 kills the busiest engine mid-stream under MULTI-LoRA +
CONSTRAINED traffic (ISSUE 16): every request decodes through a
hot-loaded adapter slot AND a grammar DFA mask, the migration journal
carries the per-request FSM state, and the adoptive sibling must resume
the grammar walk mid-structure — final streams bit-identical to an
uninterrupted lone-engine run, every output grammar-valid, chunks
exactly-once, grammar mask segments fully released afterward.
Scenario 17 re-runs the kill drill with the FLIGHT RECORDER under test
(ISSUE 17): the always-armed trace ring must auto-dump the last window
of fleet timeline from crash containment — the dumped file carries the
victim requests' full per-request timelines with the export → adopt
migration hop visible and every ``(req_id, seq)`` exactly-once across
the hop — while the streams stay bit-identical to an uninterrupted run.
Scenario 18 re-runs the kill drill with the HOST KV TIER armed
(ISSUE 18): int8 quantized pages on a page-starved pool, the victim
stream PARKED (its pages in host RAM) at the kill — containment must
drain the dead engine's HostPageStore, the adoptive (equally starved)
sibling must re-serve both migrants through its own park/unpark cycle,
and the streams stay bit-identical with chunks exactly-once.
Scenario 19 drills OVERLOAD as a first-class failure mode (ISSUE 19): a
16x tiered burst against a capacity-capped fleet under a step-latency
storm plus an engine kill, with the OverloadController armed — the
brownout ladder must climb to batch-slot preemption (journal + requeue,
the migration move turned inward), the deadline-aware gate must shed
doomed work at admission, and afterwards the ladder must return to
level 0 with every request accounted exactly-once, zero leaked pages,
and the one compiled step untouched.
Scenario 20 kills the PROCESS, not an engine (ISSUE 20): a WAL-armed
fleet serves a seeded loadgen trace in a CHILD python, the parent
SIGKILLs it mid-decode and restarts it with one engine fewer —
``Router.recover`` must replay the request WAL, re-admit every
unfinished stream through the journaled re-prefill path, resume
emission after the exact seq the client's chunk file proves delivered,
and complete every stream bit-identical to an uninterrupted reference
run with zero duplicate/missing seqs and ZERO fresh XLA compiles
during recovery (the shared disk compile cache).
Each scenario asserts both the behavior
AND the telemetry (every failure path must move its counter). Exit
code 0 iff every scenario passes.

Run: JAX_PLATFORMS=cpu python tools/chaos_serve.py
CI:  the whole ladder also runs as tests/test_chaos_serve.py (slow lane).
"""
import json
import os
import shutil
import sys
import tempfile
import urllib.error
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import faults, metrics  # noqa: E402
from paddle_tpu.checkpoint import CheckpointManager  # noqa: E402
from paddle_tpu.models import LlamaForCausalLM, llama_tiny  # noqa: E402
from paddle_tpu.serving import (BackpressureError, GrammarFSM,  # noqa: E402
                                Router, ServingEngine, random_adapter,
                                toy_tokenizer)

SEED = int(os.environ.get("CHAOS_SEED", "0"))


def _model():
    paddle.seed(SEED)
    return LlamaForCausalLM(llama_tiny(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_key_value_heads=2, max_position_embeddings=64))


def _counter(name, **labels):
    fam = metrics.get_registry().get(name)
    if fam is None:
        return 0.0
    if labels and set(labels) != set(fam.label_names):
        # partial label set: aggregate the unnamed dimensions (e.g.
        # jit_compiles_total{fn=...} summed across its source split)
        return fam.sum_labels(**labels)
    return (fam.labels(**labels) if labels else fam).value


def _check(cond, what):
    if not cond:
        raise AssertionError(what)


_RNG = np.random.RandomState(7)
P5, P9, P3, P4 = (_RNG.randint(0, 128, (n,)) for n in (5, 9, 3, 4))


def scenario_nan_quarantine(model):
    """NaN in one sequence's KV: victim quarantined, mate token-identical
    to a fault-free run, pages recover, decode compiles once."""
    ref_eng = ServingEngine(model, page_size=4, max_batch_slots=2)
    rm = ref_eng.add_request(P5, max_new_tokens=8)
    ref_eng.add_request(P9, max_new_tokens=8)
    ref = ref_eng.run()

    before = _counter("paddle_tpu_serving_nan_quarantines_total")
    eng = ServingEngine(model, page_size=4, max_batch_slots=2)
    mate = eng.add_request(P5, max_new_tokens=8)
    victim = eng.add_request(P9, max_new_tokens=8)
    eng.step()
    with faults.inject("serving.decode_step",
                       call=lambda: eng.pool.poison_seq(victim),
                       times=1, seed=SEED):
        outs = eng.run()
    _check(outs[victim].finish_reason == "nan", "victim not quarantined")
    _check(list(outs[mate].token_ids) == list(ref[rm].token_ids),
           "batch-mate tokens diverged from fault-free run")
    _check(eng.pool.used_pages == 0, "pages leaked")
    _check(_counter("paddle_tpu_serving_nan_quarantines_total")
           == before + 1, "quarantine counter")
    counts = eng.compile_counts()
    _check(counts["step"] == counts["step_buckets"], "step recompiled")
    return (f"victim n_gen={outs[victim].n_gen} reason=nan; mate "
            f"token-identical ({outs[mate].n_gen} tokens)")


def scenario_pool_exhaustion(model):
    """One injected allocation failure mid-decode: victim errors out,
    everything else (including queued work) drains."""
    eng = ServingEngine(model, page_size=4, max_batch_slots=2)
    # the 4-token prompt exactly fills its prefill page, so ITS first
    # decode append draws the armed page — it is the victim
    victim = eng.add_request(P4, max_new_tokens=6)
    mate = eng.add_request(P3, max_new_tokens=6)
    queued = eng.add_request(P3, max_new_tokens=4)
    eng.step()
    with faults.inject("serving.kv_alloc",
                       raise_=faults.ResourceExhausted, times=1, seed=SEED):
        outs = eng.run()
    _check(outs[victim].finish_reason == "error", "victim not quarantined")
    _check(outs[mate].finish_reason == "length", "mate was disturbed")
    _check(outs[queued].finish_reason == "length", "queued work stranded")
    _check(eng.pool.used_pages == 0, "pages leaked")
    return "victim=error, mate+queued drained, 0 pages leaked"


def scenario_compile_retry(model):
    """A transient step-build failure is retried; buckets still compile
    exactly once each."""
    eng = ServingEngine(model, page_size=4, max_batch_slots=1)
    rid = eng.add_request(P4, max_new_tokens=3)
    before = _counter("paddle_tpu_faults_retries_total")
    with faults.inject("serving.compile_step",
                       raise_=RuntimeError("flaky build"), times=1,
                       seed=SEED):
        outs = eng.run()
    _check(outs[rid].finish_reason == "length", "request failed")
    _check(_counter("paddle_tpu_faults_retries_total") > before,
           "no retry recorded")
    counts = eng.compile_counts()
    _check(counts["step"] == counts["step_buckets"], "step recompiled")
    return "1 injected build failure, 1 retry, step compiled once/bucket"


def scenario_deadline_and_cancel(model):
    """Deadline expiry and cancel() retire with their own reasons and
    counters; pages free immediately. A deadline that lapses while still
    QUEUED retires ``"expired"`` (pages never allocated) — only admitted
    work can ``"timeout"`` (ISSUE 19)."""
    eng = ServingEngine(model, page_size=4, max_batch_slots=1)
    t_before = _counter("paddle_tpu_serving_request_timeouts_total")
    e_before = _counter("paddle_tpu_serving_expired_total")
    c_before = _counter("paddle_tpu_serving_cancellations_total")
    running = eng.add_request(P4, max_new_tokens=6)
    late = eng.add_request(P3, max_new_tokens=6, deadline_s=0.0)
    eng.step()
    cancelled = eng.add_request(P3, max_new_tokens=6)
    eng.cancel(cancelled)
    eng.slots[0].req.deadline = faults.Deadline(-1.0)  # force mid-decode
    outs = eng.run()
    _check(outs[late].finish_reason == "expired", "queued expiry")
    _check(outs[running].finish_reason == "timeout", "mid-decode timeout")
    _check(outs[cancelled].finish_reason == "cancelled", "cancel")
    _check(_counter("paddle_tpu_serving_request_timeouts_total")
           == t_before + 1, "timeout counter != exactly 1")
    _check(_counter("paddle_tpu_serving_expired_total")
           == e_before + 1, "expired counter != exactly 1")
    _check(_counter("paddle_tpu_serving_cancellations_total")
           == c_before + 1, "cancel counter != exactly 1")
    _check(eng.pool.used_pages == 0, "pages leaked")
    return "1 expiry + 1 timeout + 1 cancel, each counted exactly once"


def scenario_backpressure(model):
    """A bounded queue rejects with a retry_after_s hint, not OOM."""
    eng = ServingEngine(model, page_size=4, max_batch_slots=1, max_queue=1)
    eng.add_request(P3, max_new_tokens=2)
    try:
        eng.add_request(P3, max_new_tokens=2)
        raise AssertionError("full queue accepted a request")
    except BackpressureError as e:
        hint = e.retry_after_s
    _check(hint > 0, "no retry_after_s hint")
    eng.run()
    eng.add_request(P3, max_new_tokens=1)  # drained queue admits again
    eng.run()
    return f"rejected with retry_after_s={hint:.3f}s, recovered after drain"


def scenario_watchdog_healthz(model):
    """Latency injection trips the watchdog; /healthz goes 503 and
    recovers after healthy steps."""
    eng = ServingEngine(model, page_size=4, max_batch_slots=1,
                        watchdog_stall_s=0.005, watchdog_recovery_steps=2)
    with metrics.MetricsServer(health_cb=eng.health, port=0) as srv:
        with faults.inject("serving.step", delay_s=0.02, times=1,
                           seed=SEED):
            eng.step()
        try:
            urllib.request.urlopen(f"{srv.url}/healthz")
            raise AssertionError("/healthz stayed 200 while degraded")
        except urllib.error.HTTPError as e:
            _check(e.code == 503, f"expected 503, got {e.code}")
            _check(json.loads(e.read())["status"] == "degraded",
                   "degraded body")
        eng.step()
        eng.step()
        with urllib.request.urlopen(f"{srv.url}/healthz") as r:
            _check(r.status == 200, "no recovery")
    trips = eng.watchdog.trips
    _check(trips == 1, f"expected exactly 1 trip episode, got {trips}")
    return "tripped -> /healthz 503 -> recovered -> 200 (1 episode)"


def _trip_watchdog(engine):
    """Report one over-threshold step straight to the watchdog state
    machine — the deterministic stand-in for a stalled step (scenario 6
    drills the real latency-injection route; here the stall must hit ONE
    chosen engine of a fleet, and a sleep long enough to beat the 30 s
    default threshold has no place in a CI drill)."""
    engine.watchdog.end_step(engine.watchdog.stall_threshold_s + 1.0)


def scenario_router_failover(model):
    """Scenario 7: an engine is NaN-poisoned mid-stream AND degraded —
    the victim quarantines, every WAITING request completes on the
    sibling exactly once; with the whole fleet dark, waiting work retires
    "unavailable" instead of bouncing (no duplicates, no drops)."""
    r = Router()
    r.add_model("m", model, replicas=2, page_size=4, max_batch_slots=1,
                watchdog_recovery_steps=999)
    e0, e1 = r.engine("m/0"), r.engine("m/1")
    victim = e0.add_request(P9, max_new_tokens=8)
    e0.step()  # victim decoding in m/0's only slot
    queued = [e0.add_request(P3, max_new_tokens=3),
              e0.add_request(P4, max_new_tokens=3)]
    moved0 = _counter("paddle_tpu_router_requeued_total")
    un0 = _counter("paddle_tpu_router_unplaceable_total")
    e0.pool.poison_seq(victim)
    _trip_watchdog(e0)
    outs = r.run()
    _check(outs[victim].finish_reason == "nan", "victim not quarantined")
    _check([outs[q].finish_reason for q in queued] == ["length"] * 2,
           "requeued work did not complete on the sibling")
    _check(len(outs) == 3, "duplicate or dropped outputs")
    _check(_counter("paddle_tpu_router_requeued_total") == moved0 + 2,
           "requeue counter != exactly 2")
    _check(e0.pool.used_pages == 0 and e1.pool.used_pages == 0,
           "pages leaked")
    _check(r.states() == {"m/0": "degraded", "m/1": "healthy"},
           "gate states wrong")
    # both engines dark: a fresh waiting request has nowhere to go and
    # retires with the deterministic reason, exactly once
    b1 = e1.add_request(P9, max_new_tokens=12)
    e1.step()
    q2 = e1.add_request(P3, max_new_tokens=2)
    _trip_watchdog(e1)
    outs2 = r.run()
    _check(outs2[q2].finish_reason == "unavailable",
           "expected finish_reason=unavailable with no healthy engine")
    _check(outs2[b1].finish_reason == "length", "in-flight request lost")
    _check(_counter("paddle_tpu_router_unplaceable_total") == un0 + 1,
           "unplaceable counter != exactly 1")
    return ("victim=nan, 2 requeued once -> length on sibling; fleet dark "
            "-> unavailable exactly once")


def scenario_router_reload(model):
    """Scenario 8: rolling reload() across a live request stream — every
    request completes, every engine ends on the new checkpoint's weights,
    and decode stays compiled exactly once per engine per weight push."""
    tmp = tempfile.mkdtemp(prefix="chaos_ckpt_")
    try:
        paddle.seed(SEED + 1)
        donor = LlamaForCausalLM(llama_tiny(
            vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
            num_key_value_heads=2, max_position_embeddings=64))
        sd = donor.state_dict()
        CheckpointManager(tmp, max_to_keep=None).save(1, {"model": sd})
        # one model INSTANCE per replica (same seed, identical weights):
        # a shared instance would flip every replica at the first restore
        r = Router()
        r.add_model("m", [_model(), _model()], page_size=4,
                    max_batch_slots=1)
        live = [r.submit(p, model="m", max_new_tokens=6)
                for p in (P5, P9, P3, P4)]
        jit0 = _counter("paddle_tpu_jit_compiles_total",
                        fn="serving_step")
        ok0 = _counter("paddle_tpu_router_reloads_total", result="ok")
        summary = r.reload(tmp)
        outs = r.run()
        _check([e["result"] for e in summary["engines"]] == ["ok", "ok"],
               f"reload results: {summary}")
        _check(sorted(outs) == sorted(live),
               "live requests dropped or duplicated across reload")
        _check(all(outs[k].finish_reason == "length" for k in live),
               "a live request did not complete normally")
        k0 = next(iter(sd))
        fleet_compiles = 0
        for eng in r.engines("m"):
            _check(np.allclose(np.asarray(eng.model.state_dict()[k0]
                                          .numpy()),
                               np.asarray(sd[k0].numpy())),
                   f"engine {eng.engine_id} not on the new weights")
            counts = eng.compile_counts()
            _check(counts["step"] == counts["step_buckets"],
                   "step recompiled across the weight push")
            fleet_compiles += counts["step"]
        _check(_counter("paddle_tpu_jit_compiles_total",
                        fn="serving_step") == jit0 + fleet_compiles,
               "step compiles != one per bucket per engine")
        _check(_counter("paddle_tpu_router_reloads_total", result="ok")
               == ok0 + 2, "reload counter")
        _check(all(h.weights_step == 1 for h in r._model_handles("m")),
               "weights_step not recorded")
        return ("4 live requests completed across a 2-engine rolling "
                "push; weights=ckpt step 1 everywhere; step still "
                "1 compile/bucket/engine")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def scenario_router_least_loaded(model):
    """Scenario 9: skewed load — two long hogs pinned on engine 0. Blind
    round-robin parks half the short requests behind them; least-loaded
    dispatch steers every short to the idle sibling. Asserted on the
    queue-wait histogram (p95 AND mean) from the registry."""
    reg = metrics.get_registry()

    def drive(policy):
        r = Router()
        r.add_model("m", model, replicas=2, page_size=4,
                    max_batch_slots=1)
        # pre-warm both engines (compile prefill+decode) so the measured
        # waits are pure scheduling, not one-off XLA compile time
        for eid in ("m/0", "m/1"):
            r.engine(eid).add_request(P3, max_new_tokens=2)
            r.engine(eid).run()
        reg.reset()
        e0 = r.engine("m/0")
        for _ in range(2):  # the skew: 2 x 28-token hogs on engine 0
            e0.add_request(P3, max_new_tokens=28)
        for i in range(8):  # 8 short requests placed by `policy`
            if policy == "round-robin":
                r.engine(f"m/{i % 2}").add_request(P4, max_new_tokens=2)
            else:
                r.submit(P4, model="m", max_new_tokens=2)
        outs = r.run()
        _check(len(outs) == 10 and all(
            o.finish_reason == "length" for o in outs.values()),
            f"{policy}: workload did not drain cleanly")
        wait = reg.get("paddle_tpu_serving_queue_wait_seconds")
        return wait.quantile(0.95), wait.sum / wait.count

    p95_rr, mean_rr = drive("round-robin")
    p95_ll, mean_ll = drive("least-loaded")
    _check(p95_ll < p95_rr,
           f"least-loaded p95 {p95_ll:.4f}s !< round-robin {p95_rr:.4f}s")
    # the mean separates by ~40% structurally (half the shorts escape the
    # hogs); 0.9 keeps teeth against a regression to blind rotation while
    # tolerating CI wall-clock noise
    _check(mean_ll < 0.9 * mean_rr,
           f"least-loaded mean {mean_ll:.4f}s !< 0.9 x round-robin "
           f"{mean_rr:.4f}s")
    return (f"p95 queue-wait {p95_rr*1e3:.1f}ms (rr) -> "
            f"{p95_ll*1e3:.1f}ms (least-loaded), mean "
            f"{mean_rr*1e3:.1f}ms -> {mean_ll*1e3:.1f}ms")


def scenario_kill_engine_mid_decode(model):
    """Scenario 10 (ISSUE 7 acceptance): N sampled streaming requests;
    the busiest engine is killed at a scheduled step via the
    router.engine_step fault point. router.step() must contain the
    crash (mark down + migrate in-flight by token journal + requeue
    waiting), and every request must complete token-identical to an
    uninterrupted run with zero duplicated/missing stream chunks —
    deterministic decode makes engine death invisible to tenants."""
    specs = [(P5, 10, 0.9, 21), (P9, 9, 0.7, 22), (P3, 8, 1.1, 23)]
    # uninterrupted reference: a lone engine, same (prompt, seed, temp)
    # per request — per-request deterministic sampling makes this THE
    # oracle for the migrated run regardless of batch composition
    ref_eng = ServingEngine(model, page_size=4, max_batch_slots=2)
    ref_ids = [ref_eng.add_request(p, max_new_tokens=n, temperature=t,
                                   seed=s) for p, n, t, s in specs]
    ref_outs = ref_eng.run()
    refs = [list(ref_outs[r].token_ids) for r in ref_ids]
    _check(any(len(set(toks)) > 1 for toks in refs),
           "reference run is not actually sampling")

    r = Router()
    r.add_model("m", model, replicas=2, page_size=4, max_batch_slots=2)
    e0 = r.engine("m/0")  # the busiest engine: ALL traffic lands here
    chunks = {i: [] for i in range(len(specs))}

    def cb(i):
        return lambda rid, tok, fin, seq: chunks[i].append((seq, tok))

    rids = [e0.add_request(p, max_new_tokens=n, temperature=t, seed=s,
                           stream_cb=cb(i))
            for i, (p, n, t, s) in enumerate(specs)]
    crash0 = _counter("paddle_tpu_router_engine_crash_total",
                      engine_id="m/0", model_id="m")
    mig0 = _counter("paddle_tpu_router_migrated_total")
    req0 = _counter("paddle_tpu_router_requeued_total")
    for _ in range(3):
        r.step()  # 2 in-flight mid-decode, 1 waiting behind them
    with faults.inject("router.engine_step",
                       raise_=RuntimeError("engine killed mid-decode"),
                       times=1, seed=SEED):
        r.step()  # the scheduled kill — must NOT escape router.step()
    _check(r.states()["m/0"] == "down", "crashed engine not gated down")
    outs = r.run()
    _check(_counter("paddle_tpu_router_engine_crash_total",
                    engine_id="m/0", model_id="m") == crash0 + 1,
           "crash counter != exactly 1")
    _check(_counter("paddle_tpu_router_migrated_total") == mig0 + 2,
           "migrated counter != the 2 in-flight requests at the kill")
    _check(_counter("paddle_tpu_router_requeued_total") == req0 + 1,
           "requeue counter != the 1 waiting request at the kill")
    for i, (rid, ref) in enumerate(zip(rids, refs)):
        _check(outs[rid].finish_reason == "length",
               f"request {i} did not complete ({outs[rid].finish_reason})")
        _check(list(outs[rid].token_ids) == ref,
               f"request {i} diverged from the uninterrupted run")
        toks = [c for c in chunks[i] if c[1] is not None]
        _check([s for s, _ in toks] == list(range(len(ref))),
               f"request {i} stream chunks duplicated or missing")
        _check([t for _, t in toks] == ref,
               f"request {i} streamed tokens != final token_ids")
        _check(chunks[i][-1] == (len(ref), None),
               f"request {i} missing terminal chunk")
    _check(r._requeued == set(), "move-once marks leaked after the drill")
    _check(all(e.pool.used_pages == 0 for e in r.engines("m")),
           "pages leaked")
    return ("m/0 killed at step 4: 2 in-flight migrated + 1 waiting "
            "requeued; 3 sampled streams bit-identical to the "
            "uninterrupted run, chunks exactly-once")


def scenario_prefix_cache_failover(model):
    """Scenario 11 (ISSUE 8): prefix-heavy streaming traffic — every
    request shares a 24-token system prefix, both engines' radix caches
    hold it, and the busiest engine dies mid-decode. The migrated
    requests must re-prefill THROUGH the sibling's prefix cache
    (prefill_tokens_saved_total rises on the adoptive engine — failover
    of prefix-heavy traffic re-runs only the uncovered tail), with final
    streams bit-identical to an uninterrupted run and stream chunks
    exactly-once."""
    rng = np.random.RandomState(17)
    prefix = rng.randint(0, 128, (24,))
    suffixes = [rng.randint(0, 128, (k,)) for k in (3, 5, 2)]
    specs = [(np.concatenate([prefix, sfx]), n, t, s)
             for sfx, (n, t, s) in zip(suffixes, ((10, 0.9, 31),
                                                  (9, 0.7, 32),
                                                  (8, 1.1, 33)))]
    # uninterrupted oracle on a CACHE-LESS lone engine: deterministic
    # sampling makes it THE reference for cold, warm, and migrated runs
    ref_eng = ServingEngine(model, page_size=4, max_batch_slots=2,
                            prefix_cache=False)
    ref_ids = [ref_eng.add_request(p, max_new_tokens=n, temperature=t,
                                   seed=s) for p, n, t, s in specs]
    ref_outs = ref_eng.run()
    refs = [list(ref_outs[r].token_ids) for r in ref_ids]
    _check(any(len(set(toks)) > 1 for toks in refs),
           "reference run is not actually sampling")

    r = Router()
    r.add_model("m", model, replicas=2, page_size=4, max_batch_slots=2)
    # prefix-heavy fleet: the shared system prefix is warm on BOTH
    # engines (as it would be under routed traffic)
    for eid in ("m/0", "m/1"):
        e = r.engine(eid)
        e.add_request(np.concatenate([prefix, np.asarray([1])]),
                      max_new_tokens=1)
        e.run()
    e0, e1 = r.engine("m/0"), r.engine("m/1")
    chunks = {i: [] for i in range(len(specs))}

    def cb(i):
        return lambda rid, tok, fin, seq: chunks[i].append((seq, tok))

    rids = [e0.add_request(p, max_new_tokens=n, temperature=t, seed=s,
                           stream_cb=cb(i))
            for i, (p, n, t, s) in enumerate(specs)]
    saved1_0 = _counter("paddle_tpu_serving_prefill_tokens_saved_total",
                        engine_id="m/1", model_id="m")
    mig0 = _counter("paddle_tpu_router_migrated_total")
    for _ in range(3):
        r.step()  # 2 in-flight mid-decode, 1 waiting behind them
    with faults.inject("router.engine_step",
                       raise_=RuntimeError("engine killed mid-decode"),
                       times=1, seed=SEED):
        r.step()  # the scheduled kill
    _check(r.states()["m/0"] == "down", "crashed engine not gated down")
    outs = r.run()
    _check(_counter("paddle_tpu_router_migrated_total") == mig0 + 2,
           "migrated counter != the 2 in-flight requests at the kill")
    saved1 = _counter("paddle_tpu_serving_prefill_tokens_saved_total",
                      engine_id="m/1", model_id="m")
    # each adopted request matches the sibling's cached 24-token prefix
    # (6 full pages); the waiting one requeues and matches too
    _check(saved1 >= saved1_0 + 3 * 24,
           f"adoptive engine saved only {saved1 - saved1_0} prefill "
           f"tokens — migration did not ride the prefix cache")
    for i, (rid, ref) in enumerate(zip(rids, refs)):
        _check(outs[rid].finish_reason == "length",
               f"request {i} did not complete ({outs[rid].finish_reason})")
        _check(list(outs[rid].token_ids) == ref,
               f"request {i} diverged from the uninterrupted run")
        toks = [c for c in chunks[i] if c[1] is not None]
        _check([s for s, _ in toks] == list(range(len(ref))),
               f"request {i} stream chunks duplicated or missing")
        _check([t for _, t in toks] == ref,
               f"request {i} streamed tokens != final token_ids")
        _check(chunks[i][-1] == (len(ref), None),
               f"request {i} missing terminal chunk")
    _check(r._requeued == set(), "move-once marks leaked after the drill")
    _check(e1.pool.used_pages == 0, "pages leaked on the adoptive engine")
    return ("m/0 killed at step 4 under prefix-heavy traffic: 2 migrated "
            f"+ 1 requeued re-prefilled via m/1's cache "
            f"({int(saved1 - saved1_0)} prefill tokens saved); streams "
            "bit-identical, chunks exactly-once")


def scenario_kill_engine_mid_chunked_prefill(model):
    """Scenario 12 (ISSUE 11): the busiest engine is killed BETWEEN
    prompt chunks of a long request. Chunked-prefill progress is only a
    cache length, so the migrated request carries an EMPTY journal (no
    token had sampled yet), resumes on the sibling from its journaled
    chunk boundary — which the sibling's radix prefix cache re-covers
    (`prefill_tokens_saved_total` rises there) — and streams
    bit-identically from seq 0 with zero duplicated or missing chunks.
    A decoding tenant migrates alongside it, its stream also
    exactly-once across the hop."""
    rng = np.random.RandomState(23)
    prefix = rng.randint(0, 128, (24,))
    long_prompt = np.concatenate([prefix, rng.randint(0, 128, (20,))])
    specs = [(P5, 10, 0.9, 41), (long_prompt, 6, 0.8, 42)]
    ref_eng = ServingEngine(model, page_size=4, max_batch_slots=2,
                            prefix_cache=False)
    ref_ids = [ref_eng.add_request(p, max_new_tokens=n, temperature=t,
                                   seed=sd) for p, n, t, sd in specs]
    ref_outs = ref_eng.run()
    refs = [list(ref_outs[r].token_ids) for r in ref_ids]
    _check(any(len(set(toks)) > 1 for toks in refs),
           "reference run is not actually sampling")

    r = Router()
    # token_budget 8: the long prompt's 20 uncovered tokens need 3+
    # chunk steps, so there IS a chunk boundary to die between
    r.add_model("m", model, replicas=2, page_size=4, max_batch_slots=2,
                token_budget=8)
    for eid in ("m/0", "m/1"):  # shared prefix warm on BOTH caches
        e = r.engine(eid)
        e.add_request(np.concatenate([prefix, np.asarray([1])]),
                      max_new_tokens=1)
        e.run()
    e0, e1 = r.engine("m/0"), r.engine("m/1")
    chunks = {i: [] for i in range(len(specs))}

    def cb(i):
        return lambda rid, tok, fin, seq: chunks[i].append((seq, tok))

    dec = e0.add_request(P5, max_new_tokens=10, temperature=0.9, seed=41,
                         stream_cb=cb(0))
    r.step()
    r.step()  # the tenant is decoding
    lng = e0.add_request(long_prompt, max_new_tokens=6, temperature=0.8,
                         seed=42, stream_cb=cb(1))
    r.step()  # admit the long prompt + its first chunk
    st = next(s for s in e0.slots if s is not None
              and s.req.req_id == lng)
    _check(st.prefilling and st.pos > 24 and not st.gen,
           f"expected the long request mid-chunked-prefill at the kill "
           f"(pos={st.pos}, gen={st.gen})")
    boundary = st.pos
    saved1_0 = _counter("paddle_tpu_serving_prefill_tokens_saved_total",
                        engine_id="m/1", model_id="m")
    mig0 = _counter("paddle_tpu_router_migrated_total")
    with faults.inject("router.engine_step",
                       raise_=RuntimeError("engine killed between chunks"),
                       times=1, seed=SEED):
        r.step()  # the scheduled kill — between prompt chunks
    _check(r.states()["m/0"] == "down", "crashed engine not gated down")
    outs = r.run()
    _check(_counter("paddle_tpu_router_migrated_total") == mig0 + 2,
           "migrated counter != the decode tenant + the mid-prefill one")
    saved1 = _counter("paddle_tpu_serving_prefill_tokens_saved_total",
                      engine_id="m/1", model_id="m")
    _check(saved1 >= saved1_0 + 24,
           f"adoptive engine saved only {saved1 - saved1_0} prefill "
           f"tokens — resume did not ride the sibling's prefix cache")
    for i, (rid, ref) in enumerate(zip((dec, lng), refs)):
        _check(outs[rid].finish_reason == "length",
               f"request {i} did not complete ({outs[rid].finish_reason})")
        _check(list(outs[rid].token_ids) == ref,
               f"request {i} diverged from the uninterrupted run")
        toks = [c for c in chunks[i] if c[1] is not None]
        _check([sq for sq, _ in toks] == list(range(len(ref))),
               f"request {i} stream chunks duplicated or missing")
        _check([t for _, t in toks] == ref,
               f"request {i} streamed tokens != final token_ids")
        _check(chunks[i][-1] == (len(ref), None),
               f"request {i} missing terminal chunk")
    _check(r._requeued == set(), "move-once marks leaked after the drill")
    _check(e1.pool.used_pages == 0, "pages leaked on the adoptive engine")
    return (f"m/0 killed at chunk boundary pos={boundary} (prompt "
            f"{long_prompt.size}): mid-prefill request resumed via m/1's "
            f"cache ({int(saved1 - saved1_0)} tokens saved), both streams "
            "bit-identical, chunks exactly-once")


def scenario_thread_fuzz_control_plane(model):
    """Scenario 13: thread-fuzz the CONTROL PLANE under LockSanitizer —
    one driver thread runs submit/step/rolling-reload, a scraper hammers
    /metrics + /metrics.json + /healthz, a prober spins health()/states()
    (the any-thread half of the router's threading contract), all
    synchronized through a barrier each iteration with seeded per-thread
    jitter so the interleavings vary but reproduce. The sanitizer wraps
    the router, registry, probe-cache and watchdog locks; the drill
    passes iff ZERO lock-discipline violations were observed AND the
    fleet ends consistent (every request completed, no leaked pages)."""
    import threading
    import time

    iters = int(os.environ.get("CHAOS_FUZZ_ITERS", "200"))
    tmp = tempfile.mkdtemp(prefix="chaos_ckpt_")
    san = faults.LockSanitizer(
        order=("router", "engine", "scheduler", "pool"),
        leaves=("metrics.registry", "metrics.server.probe",
                "watchdog/0", "watchdog/1"))
    registry = metrics.get_registry()
    orig_reg_lock = None
    try:
        paddle.seed(SEED + 13)
        donor = LlamaForCausalLM(llama_tiny(
            vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
            num_key_value_heads=2, max_position_embeddings=64))
        CheckpointManager(tmp, max_to_keep=None).save(
            1, {"model": donor.state_dict()})
        r = Router()
        r.add_model("m", [_model(), _model()], page_size=4,
                    max_batch_slots=1)
        san.attach(r, "_lock", "router")
        # the registry lock is process-global: restore it in finally
        orig_reg_lock = san.attach(registry, "_lock", "metrics.registry")
        for i, eng in enumerate(r.engines("m")):
            san.attach(eng.watchdog, "_lock", f"watchdog/{i}")

        barrier = threading.Barrier(3)
        errors, live, prompts = [], [], (P5, P9, P3, P4)
        counts = {"drive": 0, "scrape": 0, "probe": 0}

        def drive(i, rng):
            if i % 5 == 0:
                live.append(r.submit(prompts[int(rng.randint(4))],
                                     model="m", max_new_tokens=2))
            r.step()
            if i % 67 == 66:  # rolling weight pushes mid-fuzz
                summary = r.reload(tmp)
                _check(all(e["result"] == "ok"
                           for e in summary["engines"]),
                       f"reload failed mid-fuzz: {summary}")

        def scrape(i, rng):
            path = ("/metrics", "/metrics.json",
                    "/healthz?engine=m/0")[i % 3]
            try:
                with urllib.request.urlopen(srv.url + path,
                                            timeout=10) as resp:
                    _check(resp.status == 200, f"{path}: {resp.status}")
            except urllib.error.HTTPError as e:
                # a scrape that lands mid-reload may read degraded: 503
                # on /healthz is consistent, a 5xx on /metrics is not
                _check(path.startswith("/healthz") and e.code == 503,
                       f"{path}: HTTP {e.code}")

        def probe(i, rng):
            h = r.health()
            _check(h.get("status") in ("ok", "degraded"),
                   f"health() shape: {h}")
            r.states()

        def worker(key, fn, idx):
            rng = np.random.RandomState(SEED * 997 + idx)
            try:
                for i in range(iters):
                    barrier.wait(timeout=60)
                    time.sleep(float(rng.uniform(0.0, 5e-4)))
                    fn(i, rng)
                    counts[key] += 1
            except threading.BrokenBarrierError:
                pass
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append((key, e))
                barrier.abort()

        with metrics.MetricsServer(health_cb=r.health, port=0) as srv:
            san.attach(srv, "_probe_lock", "metrics.server.probe")
            threads = [threading.Thread(target=worker, args=args,
                                        name=f"fuzz-{args[0]}")
                       for args in (("drive", drive, 1),
                                    ("scrape", scrape, 2),
                                    ("probe", probe, 3))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            _check(not any(t.is_alive() for t in threads),
                   "fuzz thread wedged")
        _check(not errors, f"fuzz thread failures: {errors}")
        _check(all(c == iters for c in counts.values()),
               f"threads did not complete all iterations: {counts}")
        outs = r.run()   # drain whatever the driver left in flight
        _check(sorted(outs) == sorted(live),
               "requests dropped or duplicated under fuzz")
        _check(all(outs[k].finish_reason == "length" for k in live),
               "a fuzzed request did not complete normally")
        _check(all(e.pool.used_pages == 0 for e in r.engines("m")),
               "pages leaked under fuzz")
        san.assert_clean()
        return (f"{iters} barrier-synced iterations x 3 threads "
                f"({len(live)} requests, {iters // 67} reloads, "
                f"{iters} scrapes): 0 sanitizer violations, fleet "
                "consistent")
    finally:
        if orig_reg_lock is not None:
            registry._lock = orig_reg_lock
        shutil.rmtree(tmp, ignore_errors=True)


class _SpecOracle:
    """Chaos drafter: proposes the known reference continuation for the
    prompts it was given (100% acceptance — every decode step is a full
    multi-token burst) and garbage for everyone else (0% acceptance —
    the KV rollback runs every step). Stateless, so one instance serves
    every replica, including post-migration re-drafting over
    prompt + journal."""

    def __init__(self, table):
        self.table = [(np.asarray(p).tolist(), [int(t) for t in ref])
                      for p, ref in table]

    def propose(self, ids, k=None):
        l = np.asarray(ids).tolist()
        for p, ref in self.table:
            done = len(l) - len(p)
            if 0 <= done and l[:len(p)] == p \
                    and l[len(p):] == ref[:done]:
                return np.asarray(ref[done:done + (k or 1)], np.int32)
        return np.full(k or 1, 127, np.int32)  # rejected every burst


def scenario_kill_engine_mid_spec_burst(model):
    """Scenario 14 (ISSUE 14): the kill-engine drill under SPECULATIVE
    decoding. Both replicas run spec_k=3 with a drafter that bursts
    4 tokens/step for two requests and feeds always-rejected garbage to
    the third, so at the kill the dying engine holds multi-token-burst
    progress AND a request whose every draft was rolled back. The
    migration journal is only ever committed tokens (accepted drafts
    commit inside the step; rejected ones truncate before landing), so
    every stream must end bit-identical to a lone SPEC-OFF engine —
    chunks exactly-once, drafts never leaking into a journal."""
    specs = [(P5, 10, 0.9, 21), (P9, 9, 0.7, 22), (P3, 8, 1.1, 23)]
    # the oracle is a SPEC-OFF lone engine: identical streams here prove
    # speculation + crash + migration changed no token anywhere
    ref_eng = ServingEngine(model, page_size=4, max_batch_slots=2)
    ref_ids = [ref_eng.add_request(p, max_new_tokens=n, temperature=t,
                                   seed=s) for p, n, t, s in specs]
    ref_outs = ref_eng.run()
    refs = [list(ref_outs[r].token_ids) for r in ref_ids]
    _check(any(len(set(toks)) > 1 for toks in refs),
           "reference run is not actually sampling")

    drafter = _SpecOracle([(specs[0][0], refs[0]), (specs[1][0], refs[1])])
    r = Router()
    r.add_model("m", model, replicas=2, page_size=4, max_batch_slots=2,
                spec_k=3, drafter=drafter)
    e0 = r.engine("m/0")  # the busiest engine: ALL traffic lands here
    chunks = {i: [] for i in range(len(specs))}

    def cb(i):
        return lambda rid, tok, fin, seq: chunks[i].append((seq, tok))

    rids = [e0.add_request(p, max_new_tokens=n, temperature=t, seed=s,
                           stream_cb=cb(i))
            for i, (p, n, t, s) in enumerate(specs)]
    crash0 = _counter("paddle_tpu_router_engine_crash_total",
                      engine_id="m/0", model_id="m")
    mig0 = _counter("paddle_tpu_router_migrated_total")
    req0 = _counter("paddle_tpu_router_requeued_total")
    drafted0 = _counter("paddle_tpu_serving_spec_drafted_tokens_total")
    accept0 = _counter("paddle_tpu_serving_spec_accepted_tokens_total")
    for _ in range(2):
        r.step()  # step 2 bursts both decoders to gen=5; req 2 waits
    drafted_pre = _counter(
        "paddle_tpu_serving_spec_drafted_tokens_total") - drafted0
    accept_pre = _counter(
        "paddle_tpu_serving_spec_accepted_tokens_total") - accept0
    _check(accept_pre > 0, "no accepted burst landed before the kill")
    with faults.inject("router.engine_step",
                       raise_=RuntimeError("engine killed mid-spec-burst"),
                       times=1, seed=SEED):
        r.step()  # the scheduled kill — must NOT escape router.step()
    _check(r.states()["m/0"] == "down", "crashed engine not gated down")
    # committed-tokens-only contract, visible at the kill: everything
    # streamed so far is a prefix of the spec-off oracle — an unaccepted
    # draft leaking into a journal/stream would diverge here
    for i, ref in enumerate(refs):
        got = [t for _, t in chunks[i] if t is not None]
        _check(got == ref[:len(got)],
               f"request {i} streamed a non-committed token by the kill")
    outs = r.run()
    _check(_counter("paddle_tpu_router_engine_crash_total",
                    engine_id="m/0", model_id="m") == crash0 + 1,
           "crash counter != exactly 1")
    _check(_counter("paddle_tpu_router_migrated_total") == mig0 + 2,
           "migrated counter != the 2 in-flight requests at the kill")
    _check(_counter("paddle_tpu_router_requeued_total") == req0 + 1,
           "requeue counter != the 1 waiting request at the kill")
    for i, (rid, ref) in enumerate(zip(rids, refs)):
        _check(outs[rid].finish_reason == "length",
               f"request {i} did not complete ({outs[rid].finish_reason})")
        _check(list(outs[rid].token_ids) == ref,
               f"request {i} diverged from the spec-off oracle")
        toks = [c for c in chunks[i] if c[1] is not None]
        _check([s for s, _ in toks] == list(range(len(ref))),
               f"request {i} stream chunks duplicated or missing")
        _check([t for _, t in toks] == ref,
               f"request {i} streamed tokens != final token_ids")
        _check(chunks[i][-1] == (len(ref), None),
               f"request {i} missing terminal chunk")
    drafted = _counter(
        "paddle_tpu_serving_spec_drafted_tokens_total") - drafted0
    accepted = _counter(
        "paddle_tpu_serving_spec_accepted_tokens_total") - accept0
    _check(drafted > accepted,
           "the garbage-drafted request never exercised rejection")
    _check(r._requeued == set(), "move-once marks leaked after the drill")
    _check(all(e.pool.used_pages == 0 for e in r.engines("m")),
           "pages leaked")
    return (f"m/0 killed mid-burst (drafted {int(drafted)}, accepted "
            f"{int(accepted)} incl. an always-rejected tenant): journals "
            "carried only committed tokens; 3 streams bit-identical to "
            "the spec-off run, chunks exactly-once")


def scenario_autoscale_under_burst(model):
    """Scenario 15 (ISSUE 15): the loadgen autoscaler drill. A seeded
    Poisson trace with an 8x burst window replays against a 1-engine
    fleet whose queue-depth autoscaler may grow to 3; the burst must
    scale the fleet up and the post-burst cold signal must drain it
    back to exactly 1 — strictly drain-then-remove, so every one of the
    trace's requests completes (or retires ``"unavailable"``)
    exactly-once, with zero duplicated outputs, zero leaked pages, zero
    leaked move-once marks, AND zero fresh jit compiles after the warm
    phase: every engine the scaler spawns materializes its pinned step
    shape from the shared persistent compile cache (ISSUE 14)."""
    from paddle_tpu import loadgen

    cache_dir = tempfile.mkdtemp(prefix="chaos15-compile-cache-")
    try:
        r = Router()
        r.add_model("m", model, replicas=1, page_size=4, num_pages=128,
                    max_batch_slots=4, max_model_len=64, token_budget=32,
                    min_step_tokens=32, max_queue=128,
                    compile_cache_dir=cache_dir)
        # warm phase: one request compiles THE pinned step shape
        # (min_step_tokens=token_budget -> a single grid bucket) and
        # persists it; from here on, scale-up must be compile-free
        r.submit(P5, max_new_tokens=2)
        r.run()
        cfg = loadgen.TraceConfig(
            seed=SEED + 15, num_requests=32, vocab_size=128,
            arrival_rate=8.0, burst_start=0.2, burst_duration=1.5,
            burst_factor=8.0, num_prompt_families=4, prefix_len=6,
            max_prompt_len=24, max_output_len=6,
            slow_consumer_fraction=0.05)
        trace = loadgen.generate_trace(cfg)
        scaler = loadgen.QueueDepthAutoscaler(
            r, config=loadgen.AutoscalerConfig(
                min_engines=1, max_engines=3, scale_up_depth=2.0,
                scale_down_depth=0.25, hot_steps=2, cold_steps=6,
                cooldown_steps=6))
        rep = loadgen.LoadDriver(r, trace, autoscaler=scaler).run()
        _check(rep.exactly_once,
               f"completion accounting violated: {rep.violations[:3]}")
        _check(rep.engines_peak >= 2, "the burst never scaled the fleet")
        _check(rep.engines_final == 1,
               f"fleet did not drain back to 1 ({rep.engines_final})")
        _check(rep.scale_ups >= 1 and rep.scale_downs >= 1,
               f"missing scale events (ups={rep.scale_ups}, "
               f"downs={rep.scale_downs})")
        _check(rep.scale_ups == rep.scale_downs,
               "unbalanced scale events for a fleet that returned home")
        bad = {k: v for k, v in rep.outcomes.items()
               if k not in ("stop", "length", "unavailable")}
        _check(not bad, f"requests neither completed nor retired "
               f"unavailable: {bad}")
        _check(sum(rep.outcomes.values()) == cfg.num_requests,
               "outcome count != trace size")
        _check(rep.fresh_compiles == 0,
               f"{rep.fresh_compiles} fresh compiles on scale-up "
               f"(persistent cache missed)")
        _check(all(e.pool.used_pages == 0 for e in r.engines("m")),
               "pages leaked")
        _check(r._requeued == set(), "move-once marks leaked")
        return (f"burst scaled 1->{rep.engines_peak}->1 "
                f"({rep.scale_ups} up, {rep.scale_downs} down), "
                f"{cfg.num_requests} requests exactly-once "
                f"({rep.outcomes}), 0 fresh compiles on scale-up, "
                f"goodput {rep.goodput_tok_s:.0f} tok/s")
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)


def scenario_kill_engine_mid_constrained_adapter_stream(model):
    """Scenario 16 (ISSUE 16): the kill drill under MULTI-LoRA +
    CONSTRAINED decoding. Every request samples through a hot-loaded
    adapter slot and a grammar DFA mask; the busiest engine dies after
    two decode steps, so every in-flight request is MID-STRUCTURE —
    its FSM state is a nonzero interior state that rides the migration
    journal (``resume_fsm_state``) to the sibling, which must resume
    the grammar walk where the dead engine left it. Streams must end
    bit-identical to an uninterrupted lone engine holding the same
    adapter weights, every output must validate against its grammar
    (including the FSM-driven ``"stop"``), chunks exactly-once, and the
    released mask segments must return every engine's grammar table to
    its identity row."""
    tok = toy_tokenizer(128)
    fsms = [GrammarFSM.compile(pat, tok)
            for pat in ("[ab]{1,4}", "[abc]{2,12}", "[ab]{1,6}")]
    specs = [(P5, fsms[0], 10, 0.9, 31), (P9, fsms[1], 8, 0.7, 32),
             (P3, fsms[2], 6, 1.1, 33)]
    # the oracle: a lone engine with the SAME adapter weights
    # (random_adapter is deterministic in (store shape, seed)) and the
    # same grammars, never killed — identical streams prove the crash +
    # FSM-journal migration changed no token anywhere
    ref_eng = ServingEngine(model, page_size=4, max_batch_slots=2)
    ref_eng.register_adapter("acme", random_adapter(ref_eng.adapters,
                                                    seed=16))
    ref_ids = [ref_eng.add_request(p, max_new_tokens=n, temperature=t,
                                   seed=s, adapter_id="acme", grammar=g)
               for p, g, n, t, s in specs]
    ref_outs = ref_eng.run()
    refs = [list(ref_outs[r].token_ids) for r in ref_ids]
    _check(all(g.validates(toks) for g, toks in zip(fsms, refs)),
           "oracle run produced a grammar-invalid stream")
    _check(ref_outs[ref_ids[0]].finish_reason == "stop",
           "request 0 never exercised the FSM-driven stop")

    r = Router()
    r.add_model("m", model, replicas=2, page_size=4, max_batch_slots=2)
    r.register_adapter("acme",
                       random_adapter(r.engine("m/0").adapters, seed=16),
                       model="m")
    e0 = r.engine("m/0")  # the busiest engine: ALL traffic lands here
    chunks = {i: [] for i in range(len(specs))}

    def cb(i):
        return lambda rid, tk, fin, seq: chunks[i].append((seq, tk))

    rids = [e0.add_request(p, max_new_tokens=n, temperature=t, seed=s,
                           adapter_id="acme", grammar=g, stream_cb=cb(i))
            for i, (p, g, n, t, s) in enumerate(specs)]
    crash0 = _counter("paddle_tpu_router_engine_crash_total",
                      engine_id="m/0", model_id="m")
    mig0 = _counter("paddle_tpu_router_migrated_total")
    req0 = _counter("paddle_tpu_router_requeued_total")
    gtok0 = _counter("paddle_tpu_serving_grammar_tokens_total")
    valid0 = _counter("paddle_tpu_serving_grammar_completions_total",
                      result="valid")
    invalid0 = _counter("paddle_tpu_serving_grammar_completions_total",
                        result="invalid")
    for _ in range(2):
        r.step()  # both decoders reach gen=2: mid-structure; req 2 waits
    _check(_counter("paddle_tpu_serving_grammar_tokens_total") - gtok0
           >= 4, "no grammar-masked tokens landed before the kill")
    with faults.inject(
            "router.engine_step",
            raise_=RuntimeError("engine killed mid-constrained-stream"),
            times=1, seed=SEED):
        r.step()  # the scheduled kill — must NOT escape router.step()
    _check(r.states()["m/0"] == "down", "crashed engine not gated down")
    # everything streamed so far must be a prefix of the oracle — a
    # grammar-divergent sample or a stale FSM state would diverge here
    for i, ref in enumerate(refs):
        got = [t for _, t in chunks[i] if t is not None]
        _check(got == ref[:len(got)],
               f"request {i} streamed a grammar-divergent token")
        if i < 2:  # the two decoding slots; request 2 is still queued
            _check(got and len(got) < len(ref),
                   f"request {i} not mid-structure at the kill")
    outs = r.run()
    _check(_counter("paddle_tpu_router_engine_crash_total",
                    engine_id="m/0", model_id="m") == crash0 + 1,
           "crash counter != exactly 1")
    _check(_counter("paddle_tpu_router_migrated_total") == mig0 + 2,
           "migrated counter != the 2 in-flight requests at the kill")
    _check(_counter("paddle_tpu_router_requeued_total") == req0 + 1,
           "requeue counter != the 1 waiting request at the kill")
    for i, (rid, ref, fsm) in enumerate(zip(rids, refs, fsms)):
        _check(outs[rid].finish_reason == ref_outs[ref_ids[i]]
               .finish_reason,
               f"request {i} finish_reason diverged from the oracle")
        _check(list(outs[rid].token_ids) == ref,
               f"request {i} diverged from the uninterrupted oracle")
        _check(fsm.validates(outs[rid].token_ids),
               f"request {i} completed grammar-invalid after migration")
        toks = [c for c in chunks[i] if c[1] is not None]
        _check([s for s, _ in toks] == list(range(len(ref))),
               f"request {i} stream chunks duplicated or missing")
        _check([t for _, t in toks] == ref,
               f"request {i} streamed tokens != final token_ids")
        _check(chunks[i][-1] == (len(ref), None),
               f"request {i} missing terminal chunk")
    valid = _counter("paddle_tpu_serving_grammar_completions_total",
                     result="valid") - valid0
    _check(valid == len(specs),
           f"grammar-valid completions counter moved {valid}, "
           f"want {len(specs)}")
    _check(_counter("paddle_tpu_serving_grammar_completions_total",
                    result="invalid") == invalid0,
           "a completion retired grammar-invalid")
    _check(all(len(e._grammar_segments) == 0 for e in r.engines("m")),
           "grammar mask segments leaked after the drill")
    _check(r._requeued == set(), "move-once marks leaked after the drill")
    _check(all(e.pool.used_pages == 0 for e in r.engines("m")),
           "pages leaked")
    return (f"m/0 killed mid-structure: FSM journals resumed on the "
            f"sibling, {len(specs)} adapter+grammar streams "
            "bit-identical to the uninterrupted run, every output "
            "grammar-valid, chunks exactly-once, mask segments released")


def scenario_flight_recorder_on_crash(model):
    """Scenario 17 (ISSUE 17): the kill drill with the FLIGHT RECORDER
    under test. A fresh tracer (tiny window, scenario-owned flight dir)
    is installed BEFORE the fleet is built, all sampled streaming
    traffic lands on m/0, and the busiest engine dies mid-decode. Crash
    containment must auto-dump the last window of fleet timeline: the
    dumped JSON carries each victim request's timeline with the
    export -> adopt migration hop visible and every ``(req_id, seq)``
    exactly-once ACROSS the hop (one fleet-global seq stream per
    request), the dumps counter moves with reason="crash", and the
    streams still end bit-identical to an uninterrupted run — the
    recorder observes the crash, never perturbs it."""
    from paddle_tpu.serving import tracing

    specs = [(P5, 10, 0.9, 21), (P9, 9, 0.7, 22), (P3, 8, 1.1, 23)]
    ref_eng = ServingEngine(model, page_size=4, max_batch_slots=2)
    ref_ids = [ref_eng.add_request(p, max_new_tokens=n, temperature=t,
                                   seed=s) for p, n, t, s in specs]
    ref_outs = ref_eng.run()
    refs = [list(ref_outs[r].token_ids) for r in ref_ids]

    flight_dir = tempfile.mkdtemp(prefix="chaos17_flight_")
    old = None
    try:
        # install BEFORE building the fleet: engines and router capture
        # the process tracer at construction
        old = tracing.set_tracer(tracing.RequestTracer(
            capacity=8192, flight_dir=flight_dir, window_s=120.0))
        tracer = tracing.get_tracer()
        dumps0 = _counter("paddle_tpu_trace_recorder_dumps_total",
                          reason="crash")
        r = Router()
        r.add_model("m", model, replicas=2, page_size=4,
                    max_batch_slots=2)
        e0 = r.engine("m/0")  # the busiest engine: ALL traffic here
        rids = [e0.add_request(p, max_new_tokens=n, temperature=t,
                               seed=s) for p, n, t, s in specs]
        for _ in range(3):
            r.step()  # 2 in-flight mid-decode, 1 waiting behind them
        with faults.inject("router.engine_step",
                           raise_=RuntimeError("engine killed"),
                           times=1, seed=SEED):
            r.step()  # the kill — containment must dump the recorder
        _check(r.states()["m/0"] == "down", "crashed engine not gated")
        files = sorted(os.listdir(flight_dir))
        _check(len(files) == 1,
               f"expected exactly 1 auto-dump, found {files}")
        _check("crash" in files[0], f"dump not tagged crash: {files[0]}")
        with open(os.path.join(flight_dir, files[0])) as f:
            dump = json.load(f)
        _check(dump["reason"] == "crash", "dump reason")
        _check(_counter("paddle_tpu_trace_recorder_dumps_total",
                        reason="crash") == dumps0 + 1,
               "dumps counter != exactly 1 crash dump")
        # every victim request's timeline is in the dump, with the
        # migration hop visible: exported off m/0, adopted (or
        # requeued) onto m/1, seqs contiguous ACROSS the hop
        for i, rid in enumerate(rids):
            tl = dump["requests"].get(str(rid))
            _check(tl, f"request {i} missing from the dump")
            names = [e["name"] for e in tl]
            _check("req.enqueue" in names,
                   f"request {i} dump lost its admission history")
            hop = {"req.adopt", "req.requeue"} & set(names)
            _check(hop, f"request {i} dump shows no migration hop "
                   f"({names})")
            _check(tracing.validate_events(tl) == [],
                   f"request {i} seqs not exactly-once across the hop: "
                   f"{tracing.validate_events(tl)}")
            hopper = next(e for e in tl if e["name"] in hop)
            _check(hopper["label"] == "m/1",
                   f"request {i} hop landed on {hopper['label']!r}")
        outs = r.run()
        for i, (rid, ref) in enumerate(zip(rids, refs)):
            _check(list(outs[rid].token_ids) == ref,
                   f"request {i} diverged from the uninterrupted run")
        # the full live journal (not just the dump window) stays
        # exactly-once after the drill drains
        _check(tracing.validate_events(tracer.events()) == [],
               "live journal lost exactly-once after the drill")
        _check(tracer.dropped == 0, "ring wrapped mid-drill (sizing)")
        retired = [e for e in tracer.events()
                   if e["name"] == "req.retire"
                   and e["req_id"] in set(rids)]
        _check(len(retired) == len(rids),
               f"{len(retired)} retire events for {len(rids)} requests")
        _check(r._requeued == set(), "move-once marks leaked")
        _check(all(e.pool.used_pages == 0 for e in r.engines("m")),
               "pages leaked")
        n_ev = len(dump["events"])
        return (f"m/0 killed at step 4: containment auto-dumped "
                f"{n_ev} events; all {len(rids)} victim timelines in "
                f"the file with the m/0->m/1 hop visible, seqs "
                f"exactly-once across the hop, streams bit-identical")
    finally:
        tracing.set_tracer(old)  # old None = back to lazy env default
        shutil.rmtree(flight_dir, ignore_errors=True)


def scenario_kill_engine_with_offloaded_pages(model):
    """Scenario 18 (ISSUE 18): the kill drill with the HOST KV TIER
    armed. Both replicas run int8 KV pages + host_offload on a
    page-starved pool, all traffic lands on m/0, and admission pressure
    PARKS the low-priority stream — its quantized pages live in host RAM
    — before the engine is killed. Containment must evacuate a parked
    slot exactly like a resident one (its resume state is only the token
    journal; host pages are abandoned KV that re-prefills on the
    sibling), the dead engine's HostPageStore must drain (no leaked host
    RAM), and the adoptive sibling — just as page-starved — must repeat
    the park/unpark dance to serve both migrants, with final streams
    bit-identical to an uncontended lone-engine run and chunks
    exactly-once."""
    specs = [(P9, 10, 0.9, 51, 5), (np.concatenate([P5, P3]), 4, 0.7,
                                    52, 0)]
    # uncontended oracle: a lone int8 engine with ample pages — park,
    # migration and re-prefill must all be invisible to the streams
    ref_eng = ServingEngine(model, page_size=4, max_batch_slots=2,
                            kv_dtype="int8")
    ref_ids = [ref_eng.add_request(p, max_new_tokens=n, temperature=t,
                                   seed=s) for p, n, t, s, _ in specs]
    ref_outs = ref_eng.run()
    refs = [list(ref_outs[r].token_ids) for r in ref_ids]
    _check(any(len(set(toks)) > 1 for toks in refs),
           "reference run is not actually sampling")

    r = Router()
    # 7 usable pages vs 5+3 worst-case pages: the two requests can
    # never be resident together — parking is the only way both serve
    r.add_model("m", model, replicas=2, page_size=4, num_pages=8,
                max_batch_slots=3, kv_dtype="int8", host_offload=True)
    e0, e1 = r.engine("m/0"), r.engine("m/1")
    chunks = {i: [] for i in range(len(specs))}

    def cb(i):
        return lambda rid, tk, fin, seq: chunks[i].append((seq, tk))

    off0 = _counter("paddle_tpu_serving_kv_offload_pages_total",
                    engine_id="m/0", model_id="m")
    mig0 = _counter("paddle_tpu_router_migrated_total")
    p0, n0, t0, s0, pr0 = specs[0]
    lo = e0.add_request(p0, max_new_tokens=n0, temperature=t0, seed=s0,
                        priority=pr0, stream_cb=cb(0))
    r.step()
    r.step()  # lo is decoding and holds the pool's worst-case pages
    p1, n1, t1, s1, pr1 = specs[1]
    hi = e0.add_request(p1, max_new_tokens=n1, temperature=t1, seed=s1,
                        priority=pr1, stream_cb=cb(1))
    r.step()  # pressure parks lo; hi admits against its pages
    _check(e0.pool.offloaded_pages(lo) > 0,
           "pressure never parked the low-priority stream")
    _check(_counter("paddle_tpu_serving_kv_offload_pages_total",
                    engine_id="m/0", model_id="m") > off0,
           "offload counter never moved")
    with faults.inject("router.engine_step",
                       raise_=RuntimeError("engine killed while parked"),
                       times=1, seed=SEED):
        r.step()  # the kill — a parked slot is among the victims
    _check(r.states()["m/0"] == "down", "crashed engine not gated down")
    # the dead engine's host tier must drain with the evacuation: host
    # RAM holding abandoned quantized pages is a leak, not a tier
    _check(e0.pool.offloaded_pages() == 0,
           "dead engine's HostPageStore leaked offloaded pages")
    _check(e0.pool.used_pages == 0, "dead engine leaked HBM pages")
    outs = r.run()
    _check(_counter("paddle_tpu_router_migrated_total") == mig0 + 2,
           "migrated counter != the 2 in-flight requests at the kill")
    for i, (rid, ref) in enumerate(zip((lo, hi), refs)):
        _check(outs[rid].finish_reason == "length",
               f"request {i} did not complete ({outs[rid].finish_reason})")
        _check(list(outs[rid].token_ids) == ref,
               f"request {i} diverged from the uncontended run")
        toks = [c for c in chunks[i] if c[1] is not None]
        _check([sq for sq, _ in toks] == list(range(len(ref))),
               f"request {i} stream chunks duplicated or missing")
        _check([t for _, t in toks] == ref,
               f"request {i} streamed tokens != final token_ids")
        _check(chunks[i][-1] == (len(ref), None),
               f"request {i} missing terminal chunk")
    _check(e1.pool.used_pages == 0 and e1.pool.offloaded_pages() == 0,
           "adoptive engine leaked pages across its own park/unpark")
    _check(_counter("paddle_tpu_serving_kv_prefetch_late_total",
                    engine_id="m/1", model_id="m") == 0,
           "a prefetch landed late inside the step path on the sibling")
    _check(r._requeued == set(), "move-once marks leaked after the drill")
    counts = e1.compile_counts()
    _check(counts["step"] == counts["step_buckets"],
           "quantized step recompiled on the adoptive engine")
    return ("m/0 killed with a PARKED int8 stream: host store drained, "
            "both migrants re-served through m/1's own park/unpark, "
            "streams bit-identical, chunks exactly-once")


def scenario_brownout_under_burst(model):
    """Scenario 19 (ISSUE 19): overload survived by POLICY, not
    capacity. A 16x-burst tiered trace replays against a capacity-CAPPED
    2-engine fleet (no autoscaler) under a pinned fault schedule — a
    step-latency storm covering the burst plus an engine kill with timed
    revival — with the OverloadController armed. The brownout ladder
    must CLIMB to slot preemption (level >= 3: batch-tier decodes are
    journaled and requeued, the migration move turned inward), the
    deadline-aware gate must shed doomed standard work at admission with
    honest retry hints, and after the storm the ladder must walk fully
    BACK DOWN: final level 0, every preempted stream re-served, zero
    leaked pages, zero move-once marks, the one compiled step never
    recompiled, and every one of the trace's requests accounted
    exactly-once across admitted/shed/expired outcomes."""
    from paddle_tpu import loadgen
    from paddle_tpu.serving import (OverloadConfig, OverloadController,
                                    RetryBudget, tracing)

    r = Router(retry_budget=RetryBudget(capacity=16.0,
                                        refill_per_step=1.0))
    r.add_model("m", model, replicas=2, page_size=4, num_pages=128,
                max_batch_slots=8, max_model_len=64, token_budget=32,
                min_step_tokens=32, max_queue=128)
    for h in r.handles("m"):
        h.engine.add_request(P4, max_new_tokens=2)
        h.engine.run()
    tiers = (
        loadgen.TierSpec("interactive", priority=0, weight=0.15,
                         ttft_slo_s=1.5, itl_slo_s=0.5),
        loadgen.TierSpec("standard", priority=1, weight=0.5185,
                         deadline_s=6.0, ttft_slo_s=2.0, itl_slo_s=1.0),
        loadgen.TierSpec("batch", priority=2, weight=0.3315,
                         ttft_slo_s=10.0, itl_slo_s=5.0),
    )
    cfg = loadgen.TraceConfig(
        seed=SEED, num_requests=64, vocab_size=128,
        arrival_rate=8.0, burst_start=0.3, burst_duration=1.5,
        burst_factor=16.0, num_prompt_families=6, prefix_len=8,
        max_prompt_len=28, output_len_mean=24.0, output_len_sigma=0.5,
        max_output_len=32, slow_consumer_fraction=0.05, tiers=tiers)
    trace = loadgen.generate_trace(cfg)
    schedule = loadgen.FaultSchedule([
        loadgen.FaultEvent(t_s=0.1, kind="latency", delay_s=0.07,
                           steps=300),
        loadgen.FaultEvent(t_s=0.6, kind="kill", engine_index=0,
                           down_s=0.6),
    ])
    ctl = OverloadController(r, config=OverloadConfig(
        hot_backlog_s=0.12, cold_backlog_s=0.08, hot_steps=1,
        cold_steps=6, cooldown_steps=3, batch_chunk_cap=4))
    rep = loadgen.LoadDriver(r, trace, overload=ctl,
                             fault_schedule=schedule, step_dt=0.02).run()
    _check(rep.exactly_once,
           f"completion accounting violated: {rep.violations[:3]}")
    peak = max([lv for _, lv in ctl.events], default=0)
    _check(peak >= 3, f"ladder never reached preemption (peak={peak})")
    _check(ctl.level == 0,
           f"ladder did not walk back down (final={ctl.level})")
    _check(rep.outcomes.get("shed", 0) > 0,
           "the admission gate never shed doomed work")
    _check(_counter("paddle_tpu_serving_requests_total",
                    event="preempted") > 0,
           "no batch-tier slot was ever preempted")
    evs = {e["name"] for e in tracing.get_tracer().events()}
    _check({"req.shed", "req.preempt", "brownout.level"} <= evs,
           f"overload trace events missing: {evs}")
    bad = {k: v for k, v in rep.outcomes.items()
           if k not in ("stop", "length", "shed", "expired", "timeout",
                        "unavailable")}
    _check(not bad, f"unknown outcomes: {bad}")
    _check(sum(rep.outcomes.values()) == cfg.num_requests,
           "outcome count != trace size")
    inter = rep.tiers["interactive"].ttft_attainment
    _check(inter is not None and inter >= 0.75,
           f"interactive tier missed its TTFT SLO in the storm "
           f"({inter}) — the ladder exists to prevent exactly this")
    _check(all(e.pool.used_pages == 0 for e in r.engines("m")),
           "pages leaked")
    _check(r._requeued == set(), "move-once marks leaked")
    for e in r.engines("m"):
        counts = e.compile_counts()
        _check(counts["step"] == counts["step_buckets"],
               "brownout action recompiled the step")
    return (f"ladder 0->{peak}->0 ({len(ctl.events)} transitions), "
            f"outcomes {dict(sorted(rep.outcomes.items()))}, "
            f"interactive TTFT attainment {inter:.2f}, "
            f"0 leaked pages, step compiled once")


# ── 20. durable serving: SIGKILL the serving PROCESS mid-decode ──────────


def scenario_kill_serving_process(model):
    """ISSUE 20 acceptance: the request WAL survives PROCESS death.

    A child python serves a seeded trace behind ``Router(wal_dir=...)``,
    journaling admissions + every committed token batch (one fsync per
    step) and appending each DELIVERED chunk to a file — the file is the
    client. The parent SIGKILLs it mid-decode, then restarts the fleet
    with ONE ENGINE FEWER; ``Router.recover`` replays the WAL and
    resumes every stream after the cursor the chunk file proves
    delivered. Every completed stream must be bit-identical to an
    uninterrupted reference run, seqs exactly-once (no dup, no gap),
    with at least one stream genuinely resumed mid-decode and ZERO
    fresh XLA compiles paid during recovery (shared disk compile
    cache)."""
    from paddle_tpu.loadgen import restart

    workdir = tempfile.mkdtemp(prefix="chaos-wal-")
    try:
        res = restart.run_restart_drill(
            workdir, replicas_before=2, replicas_after=1,
            num_requests=6, kill_after_chunks=8)
        ref = restart.streams_by_index(res["ref_chunks"])
        full = restart.streams_by_index(
            res["pre_chunks"] + res["post_chunks"])
        _check(res["killed_after"] < len(res["ref_chunks"]),
               "SIGKILL landed after the workload drained — not "
               "mid-decode")
        _check(set(full) == set(ref), "stream set diverged across the "
               f"restart: {sorted(full)} vs {sorted(ref)}")
        for idx, chunks in sorted(ref.items()):
            _check(full[idx] == chunks,
                   f"stream {idx} not bit-identical across process "
                   f"death: {full[idx]} vs {chunks}")
            seqs = [s for _, _, s in full[idx]]
            _check(seqs == list(range(len(seqs))),
                   f"stream {idx} seqs not exactly-once: {seqs}")
        timing = res["timing"]
        resumed = timing.get("outcomes", {}).get("resumed", 0)
        _check(resumed >= 1,
               f"no stream resumed mid-decode (outcomes "
               f"{timing.get('outcomes')}) — the drill proved nothing")
        _check(timing["fresh_compiles"] == 0,
               f"recovery paid {timing['fresh_compiles']} fresh XLA "
               "compiles — the disk compile cache was cold")
        _check(res["rto_s"] is not None, "no recovered token observed")
        return (f"{len(ref)} streams bit-identical across SIGKILL "
                f"(killed at chunk {res['killed_after']}, {resumed} "
                f"resumed on a 2->1 engine fleet), 0 fresh compiles, "
                f"RTO {res['rto_s']:.2f}s")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


SCENARIOS = [
    ("nan-quarantine-no-poison", scenario_nan_quarantine),
    ("page-pool-exhaustion-drain", scenario_pool_exhaustion),
    ("compile-failure-retry", scenario_compile_retry),
    ("deadline-and-cancel", scenario_deadline_and_cancel),
    ("queue-backpressure", scenario_backpressure),
    ("watchdog-healthz", scenario_watchdog_healthz),
    ("router-failover-requeue-once", scenario_router_failover),
    ("router-rolling-reload", scenario_router_reload),
    ("router-least-loaded-dispatch", scenario_router_least_loaded),
    ("kill-engine-mid-decode", scenario_kill_engine_mid_decode),
    ("prefix-cache-failover-migration", scenario_prefix_cache_failover),
    ("kill-engine-mid-chunked-prefill",
     scenario_kill_engine_mid_chunked_prefill),
    ("thread-fuzz-control-plane", scenario_thread_fuzz_control_plane),
    ("kill-engine-mid-spec-burst", scenario_kill_engine_mid_spec_burst),
    ("autoscale-under-burst", scenario_autoscale_under_burst),
    ("kill-engine-mid-constrained-adapter-stream",
     scenario_kill_engine_mid_constrained_adapter_stream),
    ("flight-recorder-on-crash", scenario_flight_recorder_on_crash),
    ("kill-engine-with-offloaded-pages",
     scenario_kill_engine_with_offloaded_pages),
    ("brownout-under-burst", scenario_brownout_under_burst),
    ("kill-serving-process-mid-decode", scenario_kill_serving_process),
]


def main() -> int:
    model = _model()
    print(f"chaos_serve: seed={SEED}, {len(SCENARIOS)} scenarios\n")
    failures = 0
    for name, fn in SCENARIOS:
        faults.reset()
        try:
            detail = fn(model)
            print(f"  PASS  {name:<28} {detail}")
        except Exception as e:  # noqa: BLE001 — report, don't crash
            failures += 1
            print(f"  FAIL  {name:<28} {e!r}")
    faults.reset()
    injected = _counter("paddle_tpu_faults_injected_total",
                        point="serving.decode_step")
    print(f"\nfault points armed this run: "
          f"{sorted(faults.known_points())}")
    print(f"injected (decode_step alone): {int(injected)}; full telemetry: "
          f"python tools/metrics_dump.py --demo")
    verdict = "RESILIENT" if failures == 0 else f"{failures} FAILURE(S)"
    print(f"verdict: {verdict}")
    return 0 if failures == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
