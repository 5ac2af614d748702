"""Tests for the bench tooling (tools/_bench_timing.py and the resume
logic in tools/bench_flash.py) — the plumbing that decides what gets
measured and banked on scarce chip time. The TPU-or-exit check and the
compile-cache helper are covered in tests/test_chip_smoke.py.
"""
import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")


def _load(name, fname):
    """Load a tools/ module by path with tools/ on sys.path only for the
    duration of the load (module-level inserts leak into every later test
    — the scoping precedent is tests/test_api_fingerprint.py)."""
    sys.path.insert(0, TOOLS)
    try:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(TOOLS, fname))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    finally:
        sys.path.remove(TOOLS)


def test_iter_notes_rows_skips_bad_lines(tmp_path):
    iter_notes_rows = _load("bt_test", "_bench_timing.py").iter_notes_rows

    p = tmp_path / "notes.json"
    p.write_text('{"a": 1}\nnot json\n{"b": 2}\n')
    assert list(iter_notes_rows(str(p))) == [{"a": 1}, {"b": 2}]
    assert list(iter_notes_rows(str(tmp_path / "missing.json"))) == []


def test_summarize_s_best_block_and_missing_sides():
    bf = _load("bf_test", "bench_flash.py")
    res = {
        (1024, "xla", None): (0.006, 0.00637),
        (1024, "pallas", (1024, 1024)): (0.0005, 0.001),
        (1024, "pallas", (512, 512)): (0.0009, 0.0017),
        (2048, "xla", None): (0.01, 0.0111),
    }
    e = bf._summarize_s(res, 1024)
    assert e == {"xla_ms": 6.37, "pallas_ms": 1.0,
                 "best_blocks": [1024, 1024], "pallas_wins": True}
    assert bf._summarize_s(res, 2048) is None  # pallas side all failed
    assert bf._summarize_s(res, 4096) is None  # S never measured


def test_flash_resume_reps_gating(tmp_path):
    """The skip must honor reps with newest-row-wins: a reps=9 tie-break
    re-measures an S banked only at reps=3, and a --force reps=3
    re-measure supersedes an older reps=9 row (the r5 session-3 review
    findings, pinned)."""
    rows = [
        {"metric": "flash_ab_summary", "device": "tpu", "D": 64,
         "reps": 9, "per_seq": {"1024": {"pallas_ms": 1.0}}},
        {"metric": "flash_ab_summary", "device": "tpu", "D": 64,
         "reps": 3, "per_seq": {"1024": {"pallas_ms": 1.2},
                                "2048": {"pallas_ms": 3.7}}},
        # rows for another D or without a reps field must never skip
        {"metric": "flash_ab_summary", "device": "tpu", "D": 128,
         "reps": 9, "per_seq": {"512": {"pallas_ms": 9.9}}},
        {"metric": "flash_ab_summary", "device": "tpu", "D": 64,
         "per_seq": {"4096": {"pallas_ms": 6.1}}},
    ]
    p = tmp_path / "notes.json"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))

    bf = _load("bf_resume_test", "bench_flash.py")
    banked_rec, banked_reps = bf._load_banked(str(p), 64)

    assert banked_rec["1024"] == {"pallas_ms": 1.2}  # newest wins
    assert "512" not in banked_rec                   # D=128 filtered out
    skip_at = lambda reps: {s for s, r in banked_reps.items() if r >= reps}
    assert skip_at(3) == {1024, 2048}
    assert skip_at(9) == set()          # tie-break re-measures
    assert 4096 not in skip_at(1)       # legacy row (no reps) never skips


def test_bench_load_row_schema_is_stable():
    """The committed BENCH_LOAD.json (the fleet-level bench artifact,
    ISSUE 15) must carry exactly the schema tools/bench_load.py pins —
    values are host-dependent, keys are the contract BENCH digests and
    future sessions rely on."""
    bl = _load("bl_test", "bench_load.py")
    with open(os.path.join(REPO, "BENCH_LOAD.json")) as f:
        row = json.load(f)

    assert set(row) == set(bl.ROW_KEYS)
    assert row["metric"] == "BENCH_LOAD"
    assert row["unit"] == "tokens/s"
    assert row["value"] > 0
    rep = row["report"]
    assert set(rep) == set(bl.REPORT_KEYS)
    assert rep["exactly_once"] is True and rep["violations"] == []
    assert sum(rep["outcomes"].values()) == rep["num_requests"]
    assert rep["engines_peak"] >= rep["engines_final"] >= 1
    assert set(rep["tiers"]) == {"interactive", "standard", "batch"}
    for tier in rep["tiers"].values():
        assert set(tier) == set(bl.TIER_KEYS)
        for k in ("ttft_attainment", "itl_attainment"):
            assert tier[k] is None or 0.0 <= tier[k] <= 1.0
        # ISSUE 17: the per-tier TTFT attribution rides along — exactly
        # the named buckets, every share a finite non-negative seconds
        bd = tier["ttft_breakdown"]
        assert bd is None or set(bd) == set(bl.BREAKDOWN_KEYS)
        if bd is not None:
            # host_overhead is an exact residual; ±1 ms is the same
            # slack the ISSUE 17 sum-acceptance bound grants
            assert all(isinstance(v, float) and v >= -1e-3
                       for v in bd.values())
    assert any(t["ttft_breakdown"] is not None
               for t in rep["tiers"].values()), \
        "committed artifact carries no TTFT attribution at all"


def test_bench_chaos_row_schema_is_stable():
    """The committed BENCH_CHAOS.json (the overload-drill artifact,
    ISSUE 19) carries exactly the schema tools/bench_load.py pins: ONE
    row holding TWO runs of the same seed-0 burst + fault schedule —
    brownout armed vs control. Latencies are host-dependent; the
    accounting invariants (exactly-once, zero leaks, compile surface
    pinned) and the drill's headline claim (the armed run protects the
    interactive tier strictly better than the unprotected control on
    the identical storm) are properties of the committed artifact and
    are asserted by value."""
    bl = _load("bl_chaos_test", "bench_load.py")
    with open(os.path.join(REPO, "BENCH_CHAOS.json")) as f:
        row = json.load(f)

    assert set(row) == set(bl.CHAOS_KEYS)
    assert row["metric"] == "BENCH_CHAOS"
    assert row["unit"] == "interactive_ttft_attainment"
    assert {e["kind"] for e in row["faults"]} == {"latency", "kill"}
    armed, control = row["armed"], row["control"]
    for run in (armed, control):
        assert set(run) == set(bl.CHAOS_RUN_KEYS)
        # the sacred invariants hold WITH the ladder armed and without
        assert run["exactly_once"] is True and run["violations"] == []
        assert run["compile_counts_stable"] is True
        assert run["leaked_pages"] == 0
        assert sum(run["outcomes"].values()) == row["num_requests"]
    # the headline: armed attainment is the row's value, >= 0.90, and
    # strictly better than the control facing the identical trace+faults
    assert row["value"] == armed["interactive_ttft_attainment"] >= 0.90
    assert (armed["interactive_ttft_attainment"]
            > control["interactive_ttft_attainment"])
    assert row["vs_baseline"] > 1.0
    # the mechanism showed up: the ladder climbed to slot preemption and
    # walked fully back down; doomed work was shed at admission and
    # queued deadline lapses retired "expired" — while the control,
    # by construction, never shed or expired anything
    assert armed["brownout_peak_level"] >= 3
    assert armed["brownout_final_level"] == 0
    assert armed["outcomes"].get("shed", 0) > 0
    assert armed["outcomes"].get("expired", 0) > 0
    assert control["brownout_peak_level"] == 0
    assert control["brownout_transitions"] == 0
    assert control["outcomes"].get("shed", 0) == 0
    assert armed["shed_rate"] > 0.0 and control["shed_rate"] == 0.0


def test_bench_recovery_row_schema_is_stable():
    """The committed BENCH_RECOVERY.json (the durable-serving artifact,
    ISSUE 20) carries exactly the schema tools/bench_load.py pins: the
    cross-process SIGKILL-and-recover drill plus the WAL's steady-state
    ITL price. Latencies (RTO, p95s) are host-dependent; the contract
    booleans — streams bit-identical across process death, seqs
    exactly-once, ZERO fresh compiles during recovery, WAL overhead
    within the 1.05x gate — are properties of the committed artifact
    and are asserted by value."""
    bl = _load("bl_recovery_test", "bench_load.py")
    with open(os.path.join(REPO, "BENCH_RECOVERY.json")) as f:
        row = json.load(f)

    assert set(row) == set(bl.RECOVERY_KEYS)
    assert row["metric"] == "BENCH_RECOVERY"
    assert row["unit"] == "seconds_rto"
    drill = row["drill"]
    assert set(drill) == set(bl.RECOVERY_DRILL_KEYS)
    # the acceptance gates of the ISSUE, frozen into the artifact
    assert drill["bit_identical"] is True
    assert drill["seqs_exactly_once"] is True
    assert drill["fresh_compiles_recovery"] == 0
    assert drill["rto_s"] is not None and drill["rto_s"] > 0
    assert row["value"] == drill["rto_s"]
    assert drill["replicas_after"] < drill["replicas_before"]
    assert drill["outcomes"].get("resumed", 0) >= 1
    assert drill["streams"] == row["num_requests"]
    overhead = row["overhead"]
    assert set(overhead) == set(bl.RECOVERY_OVERHEAD_KEYS)
    assert overhead["wal_on_p95_itl_s"] > 0
    assert overhead["wal_off_p95_itl_s"] > 0
    assert row["vs_baseline"] == overhead["itl_overhead_ratio"] <= 1.05
    # group commit: ~one fsync per router.step (the +1 is shutdown's
    # final barrier), never one per request or per token
    assert 0 < overhead["fsyncs_per_step"] <= 1.25


def test_bench_kv_row_schema_is_stable():
    """The committed BENCH_KV.json (the KV-memory-economics artifact,
    ISSUE 18) carries exactly the schema tools/bench_decode.py pins.
    Timings are host-dependent; the sizing math (users_ratio — pure
    page-byte arithmetic) and the determinism-contract booleans
    (host-tier round trip bit-exact, compile surface pinned with every
    feature armed) are NOT, so those are asserted by value."""
    bd = _load("bd_test", "bench_decode.py")
    with open(os.path.join(REPO, "BENCH_KV.json")) as f:
        row = json.load(f)

    assert set(row) == set(bd.KV_ROW_KEYS)
    assert row["metric"] == "BENCH_KV"
    assert row["unit"] == "ratio"
    rep = row["report"]
    assert set(rep) == set(bd.KV_REPORT_KEYS)
    assert set(rep["tiers"]) == {"bf16", "int8"}
    for tier in rep["tiers"].values():
        assert set(tier) == set(bd.KV_TIER_KEYS)
        assert tier["tokens_per_sec"] > 0
        assert tier["itl_matched_p95_ms"] > 0
        # the compile surface stays pinned per dtype: quantization rides
        # as dtype + scale arrays, never as new programs
        assert tier["step_compiles"] == tier["step_buckets"]
    # users/chip at one HBM budget is arithmetic, not timing: head_dim
    # 128 makes the int8 page-byte ratio (2*128)/(128+4) = 1.94x
    assert row["value"] == rep["users_ratio"] >= 1.9
    i8, bf = rep["tiers"]["int8"], rep["tiers"]["bf16"]
    assert i8["users_per_chip"] >= 1.9 * bf["users_per_chip"]
    assert i8["page_bytes"] < bf["page_bytes"]
    # quantized-attention quality guard: toleranced, not bit-checked
    assert i8["spec_acceptance_rate"] >= bf["spec_acceptance_rate"] - 0.25
    host = rep["host_tier"]
    assert set(host) == set(bd.KV_HOST_KEYS)
    assert host["parked_seen"] is True
    assert host["round_trip_bit_exact"] is True
    assert host["prefetch_late"] == 0
    assert host["prefetch_pages"] == host["offload_pages"] > 0
    arm = rep["full_arm"]
    assert set(arm) == set(bd.KV_ARM_KEYS)
    assert set(arm["features"]) == {"int8", "host_offload", "spec",
                                    "grammar"}
    assert arm["step_compiles"] == arm["step_buckets"]
    assert arm["extra_jit_compiles"] == 0


def test_bench_kv_build_row_trims_to_schema():
    """build_kv_row keeps ONLY the schema-stable keys — a report field
    added later must not silently widen the committed artifact."""
    bd = _load("bd_row_test", "bench_decode.py")
    tier = {k: 1.0 for k in bd.KV_TIER_KEYS}
    tier["extra_tier_field"] = "drop me"
    report = {k: 0 for k in bd.KV_REPORT_KEYS}
    report.update(
        users_ratio=2.14159, tiers={"bf16": tier, "int8": dict(tier)},
        host_tier={k: 0 for k in bd.KV_HOST_KEYS + ("extra_host",)},
        full_arm={k: 0 for k in bd.KV_ARM_KEYS + ("extra_arm",)},
        extra_report_field="drop me")
    row = bd.build_kv_row(report, "cfg-label", "cpu")
    assert set(row) == set(bd.KV_ROW_KEYS)
    assert row["value"] == 2.142
    assert set(row["report"]) == set(bd.KV_REPORT_KEYS)
    assert set(row["report"]["tiers"]["int8"]) == set(bd.KV_TIER_KEYS)
    assert set(row["report"]["host_tier"]) == set(bd.KV_HOST_KEYS)
    assert set(row["report"]["full_arm"]) == set(bd.KV_ARM_KEYS)


def test_bench_load_build_row_trims_to_schema():
    """build_row keeps ONLY the schema-stable keys (a LoadReport field
    added later must not silently widen the committed artifact)."""
    bl = _load("bl_row_test", "bench_load.py")
    tier = {k: 1.0 for k in bl.TIER_KEYS}
    tier["extra_tier_field"] = "drop me"
    rep = {k: 0 for k in bl.REPORT_KEYS}
    rep.update(goodput_tok_s=123.456, outcomes={"length": 2},
               tiers={"gold": tier}, violations=[], exactly_once=True,
               extra_report_field="drop me")
    row = bl.build_row(rep, "cfg-label", "cpu")
    assert set(row) == set(bl.ROW_KEYS)
    assert row["value"] == 123.5
    assert set(row["report"]) == set(bl.REPORT_KEYS)
    assert set(row["report"]["tiers"]["gold"]) == set(bl.TIER_KEYS)
