#!/usr/bin/env python
"""Fleet-level load benchmark: replay a seeded paddle_tpu.loadgen trace
against a Router fleet with the queue-depth autoscaler attached and
emit ONE ``BENCH_LOAD`` row — goodput tok/s, per-tier SLO attainment,
unavailable rate, scale trajectory — the first bench artifact that
measures the fleet, not a lone engine (ISSUE 15).

The committed ``BENCH_LOAD.json`` comes from the CPU smoke::

    JAX_PLATFORMS=cpu python tools/bench_load.py --out BENCH_LOAD.json

Fixed seed + fixed fleet: the REQUEST STREAM and the completion
accounting are reproducible (same trace bytes, same outcome counts,
exactly-once always); latencies and goodput are whatever the host does
that day, which is why ``tests/test_bench_tools.py`` asserts the
artifact's SCHEMA, never its values. Knobs ride argv/env:
``--requests/--seed/--max-engines`` (or BENCH_LOAD_REQUESTS etc.) size
the drill; the defaults finish in seconds on CPU.

The row shape follows tools/bench_decode.py (metric/value/unit/
vs_baseline/config/device) so BENCH digests treat fleet rows like
engine rows; the fleet-only evidence lands under ``"report"``.

``--chaos`` emits a BENCH_CHAOS row instead (ISSUE 19: brownout armed
vs off under the same burst + fault schedule); ``--restart`` emits a
BENCH_RECOVERY row (ISSUE 20: SIGKILL a WAL-armed child fleet
mid-decode, restart 2->1 engines, score the RTO, assert zero fresh
compiles during recovery, and price the WAL's steady-state p95 ITL
overhead against a WAL-off control — committed as
``BENCH_RECOVERY.json``, schema-pinned like the others).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# every key a BENCH_LOAD row must carry — tests/test_bench_tools.py
# pins this schema against the committed BENCH_LOAD.json
ROW_KEYS = ("metric", "value", "unit", "vs_baseline", "config", "device",
            "report")
REPORT_KEYS = ("seed", "num_requests", "goodput_tok_s", "outcomes",
               "tiers", "unavailable_rate", "timeout_rate",
               "prefix_hit_ratio", "engines_peak", "engines_final",
               "scale_ups", "scale_downs", "adapter_goodput",
               "constrained_validity", "exactly_once", "violations")
TIER_KEYS = ("requests", "ttft_slo_s", "itl_slo_s", "ttft_attainment",
             "itl_attainment", "ttft_breakdown")
# the attribution buckets a tier's ttft_breakdown carries (ISSUE 17) —
# mirrors serving.tracing.TTFT_BUCKETS, literal here so the schema is
# readable without importing the stack
BREAKDOWN_KEYS = ("queue", "compile", "cold_prefill", "warm_prefill",
                  "decode", "migration", "host_overhead")

# --chaos artifact schema (ISSUE 19): one BENCH_CHAOS row holding TWO
# runs of the SAME seed-0 burst trace + fault schedule against the same
# capacity-capped fleet — brownout armed vs brownout-off control — so
# the attainment delta is the overload controller's measured value.
# tests/test_bench_tools.py pins these against the committed
# BENCH_CHAOS.json.
CHAOS_KEYS = ("metric", "value", "unit", "vs_baseline", "config",
              "device", "seed", "num_requests", "faults", "armed",
              "control")
CHAOS_RUN_KEYS = ("goodput_tok_s", "outcomes", "shed_rate",
                  "expired_rate", "interactive_ttft_attainment",
                  "brownout_peak_level", "brownout_final_level",
                  "brownout_transitions", "retry_budget_exhausted",
                  "compile_counts_stable", "leaked_pages",
                  "exactly_once", "violations")

# --restart artifact schema (ISSUE 20): one BENCH_RECOVERY row from the
# cross-process kill-and-recover drill (paddle_tpu.loadgen.restart) —
# headline value is the RTO (SIGKILL instant to first recovered token
# landing at the client), vs_baseline is the WAL's steady-state cost
# (WAL-on p95 inter-token latency over WAL-off, same in-process
# workload). tests/test_bench_tools.py pins these against the
# committed BENCH_RECOVERY.json.
RECOVERY_KEYS = ("metric", "value", "unit", "vs_baseline", "config",
                 "device", "seed", "num_requests", "drill", "overhead")
RECOVERY_DRILL_KEYS = ("replicas_before", "replicas_after", "streams",
                       "killed_after_chunks", "bit_identical",
                       "seqs_exactly_once", "outcomes",
                       "fresh_compiles_recovery", "recover_s", "rto_s")
RECOVERY_OVERHEAD_KEYS = ("wal_on_p95_itl_s", "wal_off_p95_itl_s",
                          "itl_overhead_ratio", "requests",
                          "fsyncs_per_step")


def build_row(report_dict: dict, config_label: str, device: str) -> dict:
    """The one BENCH_LOAD row, schema-pinned: headline value is goodput
    tok/s; the LoadReport evidence (already a plain dict) rides along
    trimmed to the schema-stable keys."""
    rep = {k: report_dict[k] for k in REPORT_KEYS}
    rep["tiers"] = {
        name: {k: tier[k] for k in TIER_KEYS}
        for name, tier in report_dict["tiers"].items()}
    return {
        "metric": "BENCH_LOAD",
        "value": round(float(report_dict["goodput_tok_s"]), 1),
        "unit": "tokens/s",
        "vs_baseline": 1.0,
        "config": config_label,
        "device": device,
        "report": rep,
    }


def run_drill(seed: int, requests: int, max_engines: int):
    """Seeded heavy-tail drill: Zipf sharing + Poisson burst + slow
    consumers + mixed tiers against a 1-engine fleet the autoscaler may
    grow to ``max_engines``. Returns (LoadReport, config_label,
    device_platform)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import loadgen
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny
    from paddle_tpu.serving import Router, random_adapter

    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_key_value_heads=2, max_position_embeddings=64))
    router = Router()
    router.add_model("bench", model, replicas=1, page_size=4,
                     num_pages=128, max_batch_slots=4, max_model_len=64,
                     token_budget=32, min_step_tokens=32, max_queue=128)
    # two LoRA tenants, hot-loaded fleet-wide before traffic; the spec
    # propagates so autoscaler-spawned replicas hold them too
    store = router.engine("bench/0").adapters
    router.register_adapter("acme", random_adapter(store, seed=11),
                            model="bench")
    router.register_adapter("zen", random_adapter(store, seed=12),
                            model="bench")
    cfg = loadgen.TraceConfig(
        seed=seed, num_requests=requests, vocab_size=128,
        arrival_rate=8.0, burst_start=0.3, burst_duration=1.5,
        burst_factor=6.0, num_prompt_families=6, prefix_len=8,
        max_prompt_len=28, max_output_len=8,
        slow_consumer_fraction=0.05,
        # tenancy mixes (ISSUE 16): 50% base model, two adapter tenants;
        # a third of requests constrained to short letter runs — the
        # {1,6} lower bound keeps even a 1-token truncation grammar-valid
        adapter_mix=((None, 0.5), ("acme", 0.3), ("zen", 0.2)),
        schema_mix=((None, 0.67), ("[ab]{1,6}", 0.33)))
    trace = loadgen.generate_trace(cfg)
    scaler = loadgen.QueueDepthAutoscaler(
        router, config=loadgen.AutoscalerConfig(
            min_engines=1, max_engines=max_engines, scale_up_depth=2.0,
            scale_down_depth=0.25, hot_steps=2, cold_steps=6,
            cooldown_steps=6))
    report = loadgen.LoadDriver(router, trace, autoscaler=scaler).run()
    label = (f"llama-tiny fleet 1..{max_engines} seed={seed} "
             f"n={requests} burst=6x zipf=1.2 slow=5% "
             f"adapters=2@50% constrained=33%")
    return report, label, str(jax.devices()[0].platform)


def _chaos_tiers():
    """Deadline-bearing tier mix for the chaos drill. The interactive
    slice is deliberately SMALL (0.15): brownout protects the premium
    tier by sacrificing the rest, which is only a coherent policy when
    the premium tier alone fits the fleet's degraded capacity — if
    interactive work by itself overwhelms the storm-slowed engines, no
    admission policy can save it. The standard tier carries an
    engine-enforced deadline, so the expiry sweep and the
    deadline-aware gate both see real work.

    The interactive TTFT SLO (1.5 s) sits between what a preempting
    ladder delivers under the storm (max observed ~1.3 s: one chunked
    prefill behind at most one 70 ms-slowed step) and what a jammed
    fleet delivers (2 s+: a full long-decode residual) — below the
    physical floor no policy looks good, above the jam every policy
    does."""
    from paddle_tpu import loadgen

    return (
        loadgen.TierSpec("interactive", priority=0, weight=0.15,
                         ttft_slo_s=1.5, itl_slo_s=0.5),
        loadgen.TierSpec("standard", priority=1, weight=0.5185,
                         deadline_s=6.0, ttft_slo_s=2.0, itl_slo_s=1.0),
        loadgen.TierSpec("batch", priority=2, weight=0.3315,
                         ttft_slo_s=10.0, itl_slo_s=5.0),
    )


def run_chaos_drill(seed: int, requests: int, armed: bool) -> dict:
    """One chaos run: the seed-0 6x burst trace against a CAPACITY-
    CAPPED 2-engine fleet (no autoscaler — overload must be survived,
    not scaled away), with a seeded FaultSchedule (one engine kill with
    timed revival + one injected step-latency burst) riding the replay.
    ``armed`` attaches the OverloadController; the control run faces
    the identical trace and faults without it. Resets the metrics
    registry and tracer first so the two runs score in isolation."""
    import paddle_tpu as paddle
    from paddle_tpu import faults, loadgen, metrics
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny
    from paddle_tpu.serving import (OverloadConfig, OverloadController,
                                    RetryBudget, Router, tracing)

    metrics.get_registry().reset()
    tracing.get_tracer().reset()
    faults.reset()
    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_key_value_heads=2, max_position_embeddings=64))
    router = Router(retry_budget=RetryBudget(capacity=16.0,
                                             refill_per_step=1.0))
    # host_offload stays OFF: this drill is slots-scarce, not
    # pages-scarce (128 pages x 4 tokens covers every stream), and
    # brownout-parking a batch decode would FREEZE its slot for the
    # storm — the page-pressure tier is proven in tests/chaos instead
    router.add_model("chaos", model, replicas=2, page_size=4,
                     num_pages=128, max_batch_slots=8, max_model_len=64,
                     token_budget=32, min_step_tokens=32, max_queue=128)
    # warm the one compiled step per engine BEFORE traffic (a
    # production fleet restores executables from the PR 14 disk cache):
    # without this, the first interactive arrivals pay the cold compile
    # and both runs miss the same SLOs for reasons no overload policy
    # can touch
    import numpy as np
    for h in router.handles("chaos"):
        h.engine.add_request(np.arange(4, dtype=np.int32),
                             max_new_tokens=2)
        h.engine.run()
    cfg = loadgen.TraceConfig(
        seed=seed, num_requests=requests, vocab_size=128,
        arrival_rate=8.0, burst_start=0.3, burst_duration=1.5,
        burst_factor=16.0, num_prompt_families=6, prefix_len=8,
        # LONG decodes (mean 24 vs BENCH_LOAD's 8): with a queue-jumping
        # priority tier, interactive TTFT in a jam is the RESIDUAL of
        # the earliest-finishing in-service stream — queue depth is
        # irrelevant, hold duration is everything. Long holds are what
        # the preempting ladder relieves and what buries the control.
        max_prompt_len=28, output_len_mean=24.0, output_len_sigma=0.5,
        max_output_len=32,
        slow_consumer_fraction=0.05, tiers=_chaos_tiers())
    trace = loadgen.generate_trace(cfg)
    # the incident, pinned (not drawn) so the artifact is legible: a
    # step-latency storm covering the whole arrival burst — every
    # engine step pays +70 ms, so a long decode holds its slot for
    # ~2 s of wall time and slot contention becomes the fight — plus
    # one engine kill mid-burst with timed revival (its migrated
    # streams land on the survivor mid-storm)
    schedule = loadgen.FaultSchedule([
        loadgen.FaultEvent(t_s=0.1, kind="latency", delay_s=0.07,
                           steps=300),
        loadgen.FaultEvent(t_s=0.6, kind="kill", engine_index=0,
                           down_s=0.6),
    ])
    ctl = None
    if armed:
        # asymmetric hysteresis — climb fast, descend slow: at
        # interactive-only the shed itself empties the queue, and a
        # symmetric controller would read that as recovery, de-escalate
        # mid-storm, re-admit the flood, and flap
        ctl = OverloadController(router, config=OverloadConfig(
            hot_backlog_s=0.12, cold_backlog_s=0.08, hot_steps=1,
            cold_steps=6, cooldown_steps=3, batch_chunk_cap=4))
    # step_dt MUST be fine-grained here: the default (2/arrival_rate =
    # 0.25 s/sweep) collapses the whole burst into ~6 sweeps, which
    # both dumps ~10 arrivals per sweep and gives the ladder (one
    # observe per sweep) no time to climb before the storm has passed
    report = loadgen.LoadDriver(router, trace, overload=ctl,
                                fault_schedule=schedule,
                                step_dt=0.02).run()
    cc = [h.engine.compile_counts() for h in router.handles("chaos")]
    leaked = sum(h.engine.pool.used_pages for h in router.handles("chaos"))
    reg = metrics.get_registry()
    fam = reg.get("paddle_tpu_router_retry_budget_exhausted_total")
    exhausted = int(fam.value) if fam is not None else 0
    inter = report.tiers["interactive"].ttft_attainment
    return {
        "goodput_tok_s": round(report.goodput_tok_s, 1),
        "outcomes": report.outcomes,
        "shed_rate": round(report.shed_rate, 4),
        "expired_rate": round(report.expired_rate, 4),
        "interactive_ttft_attainment": (None if inter is None
                                        else round(inter, 4)),
        "brownout_peak_level": (0 if ctl is None else
                                max([lv for _, lv in ctl.events],
                                    default=0)),
        "brownout_final_level": 0 if ctl is None else ctl.level,
        "brownout_transitions": 0 if ctl is None else len(ctl.events),
        "retry_budget_exhausted": exhausted,
        "compile_counts_stable": all(c["step"] == c["step_buckets"]
                                     for c in cc),
        "leaked_pages": int(leaked),
        "exactly_once": report.exactly_once,
        "violations": report.violations,
        "_schedule": schedule,   # stripped by build_chaos_row
    }


def build_chaos_row(seed: int, requests: int, armed: dict, control: dict,
                    device: str) -> dict:
    """The one BENCH_CHAOS row, schema-pinned: headline value is the
    ARMED run's interactive TTFT attainment; ``vs_baseline`` is the
    multiple over the brownout-off control on the identical trace and
    fault schedule."""
    schedule = armed.pop("_schedule")
    control.pop("_schedule", None)
    a = armed["interactive_ttft_attainment"] or 0.0
    c = control["interactive_ttft_attainment"] or 0.0
    return {
        "metric": "BENCH_CHAOS",
        "value": round(a, 4),
        "unit": "interactive_ttft_attainment",
        "vs_baseline": round(a / c, 2) if c else None,
        "config": (f"llama-tiny fleet=2 (capped) seed={seed} "
                   f"n={requests} burst=16x kills=1 latency=1 "
                   f"brownout-on vs brownout-off"),
        "device": device,
        "seed": seed,
        "num_requests": requests,
        "faults": [{"t_s": round(e.t_s, 3), "kind": e.kind,
                    "down_s": e.down_s, "delay_s": e.delay_s,
                    "steps": e.steps} for e in schedule.events],
        "armed": armed,
        "control": control,
    }


def _measure_itl(wal_dir, requests: int, cache_dir=None) -> dict:
    """One in-process run of the restart-drill workload on a 1-engine
    fleet, timing every stream chunk delivery: returns the p95
    inter-token gap plus the WAL's fsync-per-step evidence (group
    commit = ONE fsync per ``router.step()`` no matter how many
    requests landed tokens). ``wal_dir=None`` is the WAL-off control.
    ``cache_dir`` shares one disk compile cache across runs — without
    it every run pays its own fresh XLA compiles mid-step (the
    in-process memory cache does not span routers) and seconds of
    compile noise drown the microseconds of fsync under measurement."""
    import time as _time

    import numpy as np

    from paddle_tpu import metrics
    from paddle_tpu.loadgen import restart
    from paddle_tpu.loadgen.trace import TraceConfig, generate_trace

    router = restart.build_router(wal_dir, replicas=1,
                                  compile_cache_dir=cache_dir)
    arrivals: dict = {}

    def _cb(idx):
        def cb(rid, tok, fin, seq):
            if tok is not None:
                arrivals.setdefault(idx, []).append(_time.perf_counter())
        return cb

    trace = generate_trace(TraceConfig(
        num_requests=requests, **restart._TRACE_KW))
    for tr in trace.requests:
        router.submit(np.asarray(tr.prompt, np.int32),
                      model=restart.MODEL_ID,
                      max_new_tokens=tr.max_new_tokens,
                      temperature=tr.temperature, seed=tr.seed,
                      priority=tr.priority, stream_cb=_cb(tr.index))
    fam = metrics.get_registry().get("paddle_tpu_wal_fsync_seconds")
    fsync0 = fam.count if fam is not None else 0
    steps = 0
    while router.has_work:
        router.step()
        steps += 1
    router.shutdown()
    fam = metrics.get_registry().get("paddle_tpu_wal_fsync_seconds")
    fsyncs = (fam.count if fam is not None else 0) - fsync0
    gaps = [b - a for times in arrivals.values()
            for a, b in zip(times, times[1:])]
    return {"p95_itl_s": float(np.percentile(gaps, 95)) if gaps else 0.0,
            "steps": steps, "fsyncs": int(fsyncs)}


def run_recovery_drill(seed: int, requests: int) -> dict:
    """The ISSUE 20 acceptance drill, measured: (1) the cross-process
    kill-and-recover (child fleet SIGKILLed mid-decode, restarted 2->1
    engines over a shared disk compile cache) scoring RTO, recovery
    fresh-compiles, and bit-identical/exactly-once stream checks; (2)
    the WAL's steady-state overhead — the same in-process workload with
    the WAL armed vs off, comparing p95 inter-token latency (group
    commit amortizes ONE fsync per step across the whole batch)."""
    import shutil
    import tempfile

    from paddle_tpu.loadgen import restart

    workdir = tempfile.mkdtemp(prefix="bench-recovery-")
    try:
        res = restart.run_restart_drill(
            workdir, replicas_before=2, replicas_after=1,
            num_requests=requests, kill_after_chunks=8)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    ref = restart.streams_by_index(res["ref_chunks"])
    full = restart.streams_by_index(
        res["pre_chunks"] + res["post_chunks"])
    bit_identical = full == ref
    seqs_ok = all(
        [s for _, _, s in chunks] == list(range(len(chunks)))
        for chunks in full.values())
    timing = res["timing"]
    drill = {
        "replicas_before": 2, "replicas_after": 1,
        "streams": len(ref),
        "killed_after_chunks": res["killed_after"],
        "bit_identical": bit_identical,
        "seqs_exactly_once": seqs_ok,
        "outcomes": timing.get("outcomes", {}),
        "fresh_compiles_recovery": timing["fresh_compiles"],
        "recover_s": round(timing["recover_s"], 4),
        "rto_s": (None if res["rto_s"] is None
                  else round(res["rto_s"], 4)),
    }
    # overhead: one warmup run populates a shared disk compile cache,
    # then WAL-off and WAL-on measure identical warm workloads — any
    # residual delta is the WAL's append+fsync, not compile noise
    scratch = tempfile.mkdtemp(prefix="bench-recovery-itl-")
    try:
        cache = os.path.join(scratch, "xla-cache")
        _measure_itl(None, requests, cache_dir=cache)
        off = _measure_itl(None, requests, cache_dir=cache)
        on = _measure_itl(os.path.join(scratch, "wal"), requests,
                          cache_dir=cache)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    ratio = (on["p95_itl_s"] / off["p95_itl_s"]
             if off["p95_itl_s"] > 0 else None)
    overhead = {
        "wal_on_p95_itl_s": round(on["p95_itl_s"], 6),
        "wal_off_p95_itl_s": round(off["p95_itl_s"], 6),
        "itl_overhead_ratio": (None if ratio is None
                               else round(ratio, 4)),
        "requests": requests,
        "fsyncs_per_step": (round(on["fsyncs"] / on["steps"], 4)
                            if on["steps"] else None),
    }
    return {"drill": drill, "overhead": overhead}


def build_recovery_row(seed: int, requests: int, measured: dict,
                       device: str) -> dict:
    """The one BENCH_RECOVERY row, schema-pinned: headline value is the
    RTO in seconds (SIGKILL to first recovered token at the client);
    ``vs_baseline`` is the WAL-on/WAL-off p95 ITL ratio — the price of
    durability in steady state."""
    return {
        "metric": "BENCH_RECOVERY",
        "value": measured["drill"]["rto_s"],
        "unit": "seconds_rto",
        "vs_baseline": measured["overhead"]["itl_overhead_ratio"],
        "config": (f"llama-tiny wal fleet=2->1 seed={seed} "
                   f"n={requests} sigkill-mid-decode shared-xla-cache"),
        "device": device,
        "seed": seed,
        "num_requests": requests,
        "drill": measured["drill"],
        "overhead": measured["overhead"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int,
                    default=int(os.environ.get("BENCH_LOAD_SEED", "0")))
    ap.add_argument("--requests", type=int,
                    default=int(os.environ.get("BENCH_LOAD_REQUESTS",
                                               "0")) or None,
                    help="trace length (default: 32, or 64 for "
                         "--chaos)")
    ap.add_argument("--max-engines", type=int,
                    default=int(os.environ.get("BENCH_LOAD_MAX_ENGINES",
                                               "3")))
    ap.add_argument("--chaos", action="store_true",
                    help="run the ISSUE 19 chaos drill instead: the "
                         "same seed-0 burst trace + seeded fault "
                         "schedule twice (brownout armed vs off) "
                         "against a capacity-capped fleet, emitting a "
                         "BENCH_CHAOS row")
    ap.add_argument("--restart", action="store_true",
                    help="run the ISSUE 20 recovery drill instead: "
                         "SIGKILL a WAL-armed child fleet mid-decode, "
                         "restart it 2->1 engines, score RTO / zero "
                         "fresh recovery compiles / bit-identical "
                         "streams plus the WAL-on vs WAL-off p95 ITL "
                         "overhead, emitting a BENCH_RECOVERY row")
    ap.add_argument("--out", default=None,
                    help="write the row to this file (e.g. "
                         "BENCH_LOAD.json); stdout always gets it")
    args = ap.parse_args(argv)
    requests = args.requests or (64 if args.chaos else
                                 6 if args.restart else 32)

    if args.restart:
        import jax
        measured = run_recovery_drill(args.seed, requests)
        row = build_recovery_row(args.seed, requests, measured,
                                 str(jax.devices()[0].platform))
        print(json.dumps(row, indent=2, sort_keys=True))
        d, o = row["drill"], row["overhead"]
        ok = (d["bit_identical"] and d["seqs_exactly_once"]
              and d["fresh_compiles_recovery"] == 0
              and d["rto_s"] is not None)
        if not ok:
            print(f"RECOVERY DRILL FAILED: {d}", file=sys.stderr)
            return 1
        if (o["itl_overhead_ratio"] is not None
                and o["itl_overhead_ratio"] > 1.05):
            print(f"WAL ITL OVERHEAD {o['itl_overhead_ratio']}x > "
                  f"1.05x gate", file=sys.stderr)
            return 1
        if args.out:
            with open(args.out, "w") as f:
                json.dump(row, f, indent=2, sort_keys=True)
                f.write("\n")
        return 0

    if args.chaos:
        import jax
        armed = run_chaos_drill(args.seed, requests, armed=True)
        control = run_chaos_drill(args.seed, requests, armed=False)
        row = build_chaos_row(args.seed, requests, armed, control,
                              str(jax.devices()[0].platform))
        print(json.dumps(row, indent=2, sort_keys=True))
        ok = (row["armed"]["exactly_once"]
              and row["control"]["exactly_once"])
        if not ok:
            print(f"ACCOUNTING VIOLATIONS: "
                  f"{row['armed']['violations']} / "
                  f"{row['control']['violations']}", file=sys.stderr)
            return 1
        if args.out:
            with open(args.out, "w") as f:
                json.dump(row, f, indent=2, sort_keys=True)
                f.write("\n")
        return 0

    report, label, device = run_drill(args.seed, requests,
                                      args.max_engines)
    row = build_row(report.to_dict(), label, device)
    print(json.dumps(row, indent=2, sort_keys=True))
    if not report.exactly_once:
        print(f"ACCOUNTING VIOLATIONS: {report.violations}",
              file=sys.stderr)
        return 1
    if args.out:
        with open(args.out, "w") as f:
            json.dump(row, f, indent=2, sort_keys=True)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
