"""paddle_tpu.serving.tracing: per-request journal + flight recorder
(ISSUE 17).

Acceptance gates: the ring is exactly-once keyed — (req_id, seq)
unique, seqs contiguous, a wrapped ring loses only the OLDEST prefix
and counts every overwrite; ``attribute_ttft`` buckets SUM to the
measured TTFT exactly (the residual is pinned into host_overhead, not
dropped); an engine workload journals the full lifecycle and a
mid-decode engine kill leaves the migrated request's timeline ONE
contiguous seq stream across the hop; the Router auto-dumps a flight
record from crash containment and the /healthz ok→503 edge (and a
FAILING dump is contained — diagnostics lost, never requests); the
loadgen driver's per-tier ``ttft_breakdown`` means match the measured
mean TTFT within the ±1 ms acceptance bound; and overhead mirrors the
metrics disabled-registry contract — disabled emit is a flag check,
enabled emit is allocation-free in steady state.

Spans (ISSUE 25): every engine step is one ``step`` span tiled by its five
phases under the router's ``sweep``; the ``step`` span's counters are
those of the grid that was built; the same spans lie on the host plane of
a ``jax.profiler`` trace; a disabled tracer enters no annotation.
"""
import importlib.util
import json
import os
import sys
import time
import tracemalloc
from contextlib import contextmanager

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import faults, metrics
from paddle_tpu.loadgen import LoadDriver, TraceConfig, generate_trace
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.profiler import ring
from paddle_tpu.serving import (RequestTracer, Router, ServingEngine,
                                TTFT_BUCKETS, attribute_ttft, tracing,
                                validate_events)

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")


def _model(seed=0):
    paddle.seed(seed)
    return LlamaForCausalLM(llama_tiny(
        vocab_size=32, hidden_size=16, num_layers=1, num_heads=1,
        num_key_value_heads=1, max_position_embeddings=32))


_ENGINE_KW = dict(page_size=4, max_batch_slots=2)

_RNG = np.random.RandomState(7)
P3, P5 = (_RNG.randint(1, 32, (n,)) for n in (3, 5))


def _counter(name, **labels):
    fam = metrics.get_registry().get(name)
    if fam is None:
        return 0.0
    return (fam.labels(**labels) if labels else fam).value


@contextmanager
def _fresh(**kw):
    """A private process tracer, installed BEFORE the fleet is built —
    engines and the router capture ``get_tracer()`` at construction."""
    tracer = RequestTracer(**kw)
    old = tracing.set_tracer(tracer)
    try:
        yield tracer
    finally:
        tracing.set_tracer(old)


class _Clock:
    """Manually-advanced monotonic clock (the injectable-clock seam)."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _ev(t, rid, seq, name, arg=0.0, label=""):
    return {"t": t, "req_id": rid, "seq": seq, "name": name,
            "arg": arg, "label": label}


# ───────────────────────────── ring buffer ─────────────────────────────


class TestRing:
    def test_interleaved_streams_snapshot_in_seq_order(self):
        clk = _Clock()
        tr = RequestTracer(capacity=64, clock=clk)
        for i in range(5):
            clk.t = float(i)
            tr.emit("req.token", "a", arg=float(i))
            tr.emit("req.token", "b", arg=float(i), label="m/0")
        a = tr.events_for("a")
        assert [e["seq"] for e in a] == list(range(5))
        assert [e["arg"] for e in a] == [0.0, 1.0, 2.0, 3.0, 4.0]
        assert tr.events_for("b")[0]["label"] == "m/0"
        assert validate_events(tr.events()) == []
        assert tr.dropped == 0

    def test_wrap_drops_oldest_prefix_and_counts(self):
        tr = RequestTracer(capacity=16)
        for _ in range(24):
            tr.emit("req.token", "r")
        assert tr.dropped == 8
        evs = tr.events_for("r")
        assert [e["seq"] for e in evs] == list(range(8, 24))
        # a wrapped ring loses the oldest prefix, never punches a hole
        assert validate_events(evs) == []

    def test_validate_flags_dupes_and_holes(self):
        dupe = [_ev(0.0, "r", 0, "req.token"), _ev(0.1, "r", 0,
                                                   "req.token")]
        assert any("duplicate" in p for p in validate_events(dupe))
        hole = [_ev(0.0, "r", 0, "req.token"), _ev(0.2, "r", 2,
                                                   "req.token")]
        assert any("missing" in p for p in validate_events(hole))
        assert validate_events([]) == []

    def test_disabled_emit_journals_nothing(self):
        tr = RequestTracer(capacity=64, enabled=False)
        tr.emit("req.token", "r")
        assert tr.events() == [] and tr.dropped == 0

    def test_reset_forgets_events_seqs_and_drops(self):
        tr = RequestTracer(capacity=16)
        for _ in range(20):
            tr.emit("req.token", "r")
        tr.reset()
        assert tr.events() == [] and tr.dropped == 0
        tr.emit("req.token", "r")
        assert tr.events_for("r")[0]["seq"] == 0

    def test_flush_metrics_moves_drop_count_once(self):
        tr = RequestTracer(capacity=16)
        for _ in range(20):
            tr.emit("req.token", "r")
        name = "paddle_tpu_trace_dropped_events_total"
        before = _counter(name)
        tr.flush_metrics()
        assert _counter(name) == before + 4 and tr.dropped == 0
        tr.flush_metrics()  # nothing new accumulated: no double count
        assert _counter(name) == before + 4


# ─────────────────────────── TTFT attribution ───────────────────────────


class TestAttribution:
    def test_gap_classification_and_exact_sum(self):
        evs = [
            _ev(0.2, "r", 0, "req.dispatch", label="m/0"),  # host 0.2
            _ev(1.0, "r", 1, "req.admit"),                  # queue 0.8
            _ev(1.5, "r", 2, "req.compile"),                # compile 0.5
            _ev(2.0, "r", 3, "req.chunk"),                  # cold 0.5
            _ev(2.5, "r", 4, "req.token"),                  # decode 0.5
        ]
        bd = attribute_ttft(evs, t_submit=0.0, t_first=2.75)
        assert set(bd) == set(TTFT_BUCKETS)
        assert bd["queue"] == pytest.approx(0.8)
        assert bd["compile"] == pytest.approx(0.5)
        assert bd["cold_prefill"] == pytest.approx(0.5)
        assert bd["warm_prefill"] == 0.0
        assert bd["decode"] == pytest.approx(0.5)
        assert bd["migration"] == 0.0
        # dispatch gap + the post-last-event tail land in the residual
        assert bd["host_overhead"] == pytest.approx(0.2 + 0.25)
        assert sum(bd.values()) == pytest.approx(2.75, abs=1e-12)

    def test_prefix_hit_turns_prefill_warm(self):
        evs = [
            _ev(1.0, "r", 0, "req.admit"),
            _ev(1.1, "r", 1, "req.prefix_hit", arg=4.0),
            _ev(2.0, "r", 2, "req.chunk"),
        ]
        bd = attribute_ttft(evs, t_submit=0.0, t_first=2.0)
        assert bd["warm_prefill"] == pytest.approx(0.9)
        assert bd["cold_prefill"] == 0.0
        assert bd["queue"] == pytest.approx(1.1)
        assert sum(bd.values()) == pytest.approx(2.0, abs=1e-12)

    def test_migration_hop_charges_migration(self):
        evs = [
            _ev(0.5, "r", 0, "req.admit"),
            _ev(1.5, "r", 1, "req.adopt", label="m/1"),
            _ev(1.8, "r", 2, "req.chunk"),
        ]
        bd = attribute_ttft(evs, t_submit=0.0, t_first=1.8)
        assert bd["migration"] == pytest.approx(1.0)
        assert sum(bd.values()) == pytest.approx(1.8, abs=1e-12)

    def test_empty_window_is_all_host_overhead(self):
        # events outside (t_submit, t_first] — e.g. lost to ring wrap —
        # cannot silently shrink the total: the residual covers it
        evs = [_ev(9.0, "r", 7, "req.token")]
        bd = attribute_ttft(evs, t_submit=10.0, t_first=10.5)
        assert bd["host_overhead"] == pytest.approx(0.5)
        assert sum(bd.values()) == pytest.approx(0.5, abs=1e-12)


# ──────────────────────────── flight recorder ────────────────────────────


class TestFlightRecorder:
    def test_dump_windows_groups_and_counts(self, tmp_path):
        clk = _Clock()
        tr = RequestTracer(capacity=64, clock=clk,
                           flight_dir=str(tmp_path), window_s=5.0)
        tr.emit("req.enqueue", "old")       # t=0: outside the window
        clk.t = 10.0
        tr.emit("req.admit", "a")
        tr.emit("req.chunk", "a")
        tr.emit("brownout.level", "m", arg=1.0)
        before = _counter("paddle_tpu_trace_recorder_dumps_total",
                          reason="why not+ok")
        path = tr.dump_flight(reason="why not+ok")
        assert os.path.dirname(path) == str(tmp_path)
        assert "why-not-ok" in os.path.basename(path)  # sanitized name
        with open(path) as f:
            payload = json.load(f)
        assert payload["reason"] == "why not+ok"
        assert payload["window_s"] == 5.0
        names = {e["name"] for e in payload["events"]}
        assert "req.enqueue" not in names          # windowed out
        assert names == {"req.admit", "req.chunk", "brownout.level"}
        assert [e["seq"] for e in payload["requests"]["a"]] == [0, 1]
        assert _counter("paddle_tpu_trace_recorder_dumps_total",
                        reason="why not+ok") == before + 1

    def test_dump_fault_point_raises_to_caller(self, tmp_path):
        tr = RequestTracer(capacity=16, flight_dir=str(tmp_path))
        tr.emit("req.enqueue", "r")
        with faults.inject("tracing.dump",
                           raise_=RuntimeError("disk full"), times=1):
            with pytest.raises(RuntimeError):
                tr.dump_flight(reason="boom")
        assert os.listdir(str(tmp_path)) == []  # nothing half-written


# ───────────────────── engine lifecycle journaling ─────────────────────


class TestEngineTimeline:
    def test_run_journals_full_lifecycle_exactly_once(self):
        with _fresh(capacity=4096) as tr:
            engine = ServingEngine(_model(), **_ENGINE_KW)
            rid = engine.add_request(P5, max_new_tokens=4)
            out = engine.run()[rid]
            assert out.finish_reason == "length"
            tl = tr.events_for(rid)
            assert validate_events(tl) == []
            assert tl[0]["name"] == "req.enqueue" and tl[0]["seq"] == 0
            names = [e["name"] for e in tl]
            for must in ("req.enqueue", "req.admit", "req.chunk",
                         "req.chunk_planned", "req.token", "req.retire"):
                assert must in names, must
            assert names.count("req.retire") == 1
            assert tl[-1]["name"] == "req.retire"
            assert tl[-1]["label"] == "length"
            # engine steps journal as engine-keyed ``step`` spans whose
            # ``landed`` counter adds up to the tokens streamed
            steps = [e for e in tr.events() if e["name"] == "step"]
            assert steps and all(e["req_id"] == engine.engine_id
                                 for e in steps)
            assert sum(e["counts"]["landed"] for e in steps) == 4
            assert tr.dropped == 0

    def test_warm_prefix_emits_prefix_hit(self):
        with _fresh(capacity=4096) as tr:
            engine = ServingEngine(_model(), **_ENGINE_KW)
            shared = _RNG.randint(1, 32, (8,))
            engine.add_request(np.concatenate([shared, [1]]),
                               max_new_tokens=2)
            engine.run()
            rid = engine.add_request(np.concatenate([shared, [2]]),
                                     max_new_tokens=2)
            engine.run()
            names = {e["name"] for e in tr.events_for(rid)}
            assert "req.prefix_hit" in names


# ─────────────────── migration: one contiguous timeline ───────────────────


class TestMigrationContiguity:
    def test_mid_decode_kill_keeps_one_seq_stream(self):
        with _fresh(capacity=8192) as tr:
            r = Router()
            r.add_model("m", _model(), replicas=2, page_size=4,
                        max_batch_slots=1, watchdog_recovery_steps=99)
            e0 = r.engine("m/0")
            rid = e0.add_request(P5, max_new_tokens=8, temperature=0.8,
                                 seed=3)
            e0.step()
            e0.step()  # tokens journaled before the crash
            with faults.inject("router.engine_step",
                               raise_=RuntimeError("chip died"),
                               times=1):
                r.step()
            assert r.states()["m/0"] == "down"
            outs = r.run()
            assert outs[rid].finish_reason == "length"
            tl = tr.events_for(rid)
            # the hop (export off the corpse, adopt + migrate onto the
            # sibling) continues the SAME seq stream: zero dups, zero
            # holes, exactly one terminal
            assert validate_events(tl) == []
            names = [e["name"] for e in tl]
            for must in ("req.export", "req.adopt", "req.migrate"):
                assert must in names, must
            assert names.count("req.retire") == 1
            hop = next(e for e in tl if e["name"] == "req.adopt")
            assert hop["label"] == "m/1"
            assert tr.dropped == 0

    def test_crash_containment_auto_dumps_flight(self, tmp_path):
        with _fresh(capacity=8192, flight_dir=str(tmp_path)):
            r = Router()
            r.add_model("m", _model(), replicas=2, page_size=4,
                        max_batch_slots=1, watchdog_recovery_steps=99)
            e0 = r.engine("m/0")
            rid = e0.add_request(P5, max_new_tokens=6, seed=3)
            e0.step()
            with faults.inject("router.engine_step",
                               raise_=RuntimeError("chip died"),
                               times=1):
                r.step()
            files = os.listdir(str(tmp_path))
            assert len(files) == 1 and "crash" in files[0]
            with open(os.path.join(str(tmp_path), files[0])) as f:
                payload = json.load(f)
            assert payload["reason"] == "crash"
            tl = payload["requests"][str(rid)]
            assert validate_events(tl) == []
            # the dump already shows where the victim was at death AND
            # the hop failover just emitted
            names = {e["name"] for e in tl}
            assert "req.enqueue" in names
            assert {"req.migrate", "req.requeue"} & names
            r.run()

    def test_failing_dump_never_breaks_containment(self, tmp_path):
        with _fresh(capacity=1024, flight_dir=str(tmp_path)):
            r = Router()
            r.add_model("m", _model(), replicas=2, page_size=4,
                        max_batch_slots=1, watchdog_recovery_steps=99)
            rid = r.engine("m/0").add_request(P3, max_new_tokens=4,
                                              seed=1)
            with faults.inject("router.engine_step",
                               raise_=RuntimeError("chip died"),
                               times=1):
                with faults.inject("tracing.dump",
                                   raise_=RuntimeError("disk full"),
                                   times=1):
                    r.step()  # contained: diagnostics lost, not requests
            assert r.states()["m/0"] == "down"
            assert os.listdir(str(tmp_path)) == []
            outs = r.run()
            assert outs[rid].finish_reason == "length"

    def test_healthz_dark_transition_dumps_exactly_once(self, tmp_path):
        with _fresh(capacity=1024, flight_dir=str(tmp_path)):
            r = Router()
            r.add_model("m", _model(), replicas=1, page_size=4,
                        max_batch_slots=1)
            assert r.health()["status"] == "ok"
            assert os.listdir(str(tmp_path)) == []
            r.mark_down("m/0")  # the model goes fully dark
            assert r.health()["status"] == "degraded"
            files = os.listdir(str(tmp_path))
            assert len(files) == 1 and "healthz" in files[0]
            # edge-triggered: a scraper polling a degraded fleet gets
            # ONE dump per transition, not one per scrape
            assert r.health()["status"] == "degraded"
            assert len(os.listdir(str(tmp_path))) == 1
            r.undrain("m/0")
            assert r.health()["status"] == "ok"
            r.mark_down("m/0")
            r.health()
            assert len(os.listdir(str(tmp_path))) == 2  # new transition


# ───────────────────────── chrome-trace export ─────────────────────────


def _trace_dump_mod():
    sys.path.insert(0, TOOLS)
    try:
        spec = importlib.util.spec_from_file_location(
            "td_test", os.path.join(TOOLS, "trace_dump.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    finally:
        sys.path.remove(TOOLS)


class TestChromeExport:
    def test_tracks_slices_and_counters(self):
        td = _trace_dump_mod()
        evs = [
            _ev(1.0, "a", 0, "req.enqueue", arg=5.0, label="m/0"),
            _ev(1.5, "a", 1, "req.adopt", arg=1.0, label="m/1"),
            _ev(1.9, "a", 2, "req.retire", label="length"),
            _ev(1.2, "b", 0, "req.enqueue", arg=3.0, label="m/0"),
            dict(_ev(1.1, "m/0", 0, "step", arg=0.1), span=1, parent=0,
                 counts={"rows": 4, "landed": 4}),
        ]
        doc, problems = td.chrome_trace(evs, pid=7)
        assert problems == []
        assert doc["displayTimeUnit"] == "ms"
        tracks = {e["args"]["name"] for e in doc["traceEvents"]
                  if e["ph"] == "M"}
        assert tracks == {"req a", "req b", "spans m/0"}
        # a migrated request is ONE track: its slices share a tid and
        # each gap is labeled by the event that ends it
        slices = [e for e in doc["traceEvents"]
                  if e["ph"] == "X" and e["args"].get("req_id") == "a"]
        assert len({e["tid"] for e in slices}) == 1
        hop = next(e for e in slices if e["name"] == "req.adopt")
        assert hop["ts"] == pytest.approx(1.0e6)
        assert hop["dur"] == pytest.approx(0.5e6)
        counters = [e for e in doc["traceEvents"] if e["ph"] == "C"]
        assert counters == [{
            "name": "landed/m/0", "ph": "C", "cat": "counter",
            "ts": pytest.approx(1.1e6), "pid": 7,
            "args": {"value": 4.0}}]

    def test_duplicate_seq_fails_the_audit(self):
        td = _trace_dump_mod()
        evs = [_ev(1.0, "a", 0, "req.enqueue"),
               _ev(1.1, "a", 0, "req.token")]
        _, problems = td.chrome_trace(evs)
        assert problems

    def test_load_events_reads_dump_and_bare_list(self, tmp_path):
        td = _trace_dump_mod()
        evs = [_ev(1.0, "a", 0, "req.enqueue")]
        tr = RequestTracer(capacity=16, flight_dir=str(tmp_path))
        tr.emit("req.enqueue", "a")
        path = tr.dump_flight(reason="t")
        assert [e["name"] for e in td.load_events(path)] \
            == ["req.enqueue"]
        bare = tmp_path / "bare.json"
        bare.write_text(json.dumps(evs))
        assert td.load_events(str(bare)) == evs


# ───────────────────── driver TTFT-breakdown scoring ─────────────────────


class TestDriverBreakdown:
    def test_per_tier_breakdown_sums_to_measured_mean_ttft(self):
        with _fresh(capacity=65536) as tr:
            r = Router()
            r.add_model("m", _model(), replicas=1, page_size=4,
                        num_pages=64, max_batch_slots=2,
                        max_model_len=32, token_budget=16,
                        min_step_tokens=16, max_queue=64)
            trace = generate_trace(TraceConfig(
                seed=4, num_requests=10, vocab_size=32, prefix_len=5,
                arrival_rate=50.0, max_prompt_len=16, max_output_len=4))
            hist = "paddle_tpu_loadgen_ttft_seconds"
            tiers = {t.tier for t in trace.requests}
            before = {name: (_hist_sum(hist, name),
                             _hist_count(hist, name)) for name in tiers}
            rep = LoadDriver(r, trace).run()
            assert rep.exactly_once, rep.violations
            assert validate_events(tr.events()) == []
            saw = 0
            for name, t in rep.tiers.items():
                bd = t.ttft_breakdown
                if bd is None:
                    continue
                saw += 1
                assert set(bd) == set(TTFT_BUCKETS)
                assert all(v >= -1e-3 for v in bd.values())
                # the buckets of each request sum EXACTLY to its
                # measured TTFT (shared perf_counter domain), so the
                # tier's mean breakdown must reproduce the mean TTFT
                # the histogram measured — ±1 ms is the ISSUE 17 bound
                d_sum = _hist_sum(hist, name) - before[name][0]
                d_n = _hist_count(hist, name) - before[name][1]
                assert d_n > 0
                assert sum(bd.values()) \
                    == pytest.approx(d_sum / d_n, abs=1e-3)
            assert saw > 0, "no tier carried a breakdown"
            fam = metrics.get_registry().get(
                "paddle_tpu_loadgen_ttft_breakdown_seconds")
            assert fam is not None


def _hist_sum(name, tier):
    fam = metrics.get_registry().get(name)
    return fam.labels(tier=tier).sum if fam is not None else 0.0


def _hist_count(name, tier):
    fam = metrics.get_registry().get(name)
    return fam.labels(tier=tier).count if fam is not None else 0


# ─────────────────────────────── spans ───────────────────────────────

PHASES = ("step.plan", "step.pack", "step.dispatch", "step.wait",
          "step.land")
# the phase sequences a call can have, by initial (k: pack): nothing
# launched or landed; launch + land; land only; launch k, launch k+1, land
# k; a fresh bucket lands first, then launches; the same behind a launch
# of k; launch k, plan k+1 with no row, land k
_STEP_FORMS = {"p", "pkdwl", "pwl", "pkdpkdwl", "pkwld", "pkdpkwld",
               "pkdpwl"}


def _model64(seed=0):
    paddle.seed(seed)
    return LlamaForCausalLM(llama_tiny(
        vocab_size=32, hidden_size=16, num_layers=1, num_heads=1,
        num_key_value_heads=1, max_position_embeddings=64))


def _spans(events):
    return [e for e in events if "span" in e]


def _check_step_family(step, children):
    """``children`` (in ring order) are the phases in one of the orders
    a call has (``_STEP_FORMS``), nested in their ``step``, disjoint, and
    no longer than it together."""
    names = [c["name"] for c in children]
    code = {"step.plan": "p", "step.pack": "k", "step.dispatch": "d",
            "step.wait": "w", "step.land": "l"}
    assert "".join(code[n] for n in names) in _STEP_FORMS, names
    lo, hi = step["t"] - step["arg"], step["t"]
    prev_end = lo
    for c in children:
        c0, c1 = c["t"] - c["arg"], c["t"]
        assert c["req_id"] == step["req_id"]
        assert lo <= c0 <= c1 <= hi, (c["name"], lo, c0, c1, hi)
        assert c0 >= prev_end, f"{c['name']} overlaps its predecessor"
        prev_end = c1
    assert sum(c["arg"] for c in children) <= step["arg"] + 1e-9


class TestSpans:
    def test_mixed_schedule_one_step_span_a_step_tiled_by_phases(self):
        """(a) a decode + chunk schedule through a router: one ``step``
        span per engine step, its phases in order, nested, disjoint, sum
        <= parent; every ``sweep`` encloses its ``step``."""
        with _fresh(capacity=8192) as tr:
            r = Router()
            r.add_model("m", _model64(), replicas=1, page_size=4,
                        num_pages=64, max_batch_slots=3, max_model_len=64,
                        token_budget=8)
            eng = r.engines("m")[0]
            rng = np.random.RandomState(3)
            r.submit(rng.randint(1, 32, (5,)), model="m", max_new_tokens=6)
            n_sweeps = 0
            for i in range(40):
                if i == 2:    # a 19-token prompt chunks under budget 8
                    r.submit(rng.randint(1, 32, (19,)), model="m",
                             max_new_tokens=3)
                if not r.has_work:
                    break
                r.step()
                n_sweeps += 1
            assert not r.has_work
            r.step()          # an idle sweep steps no engine
            evs = tr.events()
        assert validate_events(evs) == []
        spans = _spans(evs)
        steps = [e for e in spans if e["name"] == "step"]
        sweeps = {e["span"]: e for e in spans if e["name"] == "sweep"}
        assert len(steps) == eng.stats["steps"] == n_sweeps
        assert len(sweeps) == n_sweeps + 1
        kinds = set()
        for st in steps:
            kids = [e for e in spans if e["parent"] == st["span"]]
            _check_step_family(st, kids)
            sw = sweeps[st["parent"]]
            assert sw["t"] - sw["arg"] <= st["t"] - st["arg"]
            assert st["t"] <= sw["t"]
            c = st["counts"]
            assert c["rows"] == (c["decode_rows"] + c["chunk_rows"]
                                 + c["draft_rows"]) <= c["bucket"]
            assert c["kv_walked"] >= c["kv_held"] >= c["seqs"]
            kinds.add((c["decode_rows"] > 0, c["chunk_rows"] > 0))
        # the schedule really mixed: chunk-only, decode-only, both
        assert {(False, True), (True, False), (True, True)} <= kinds
        assert sum(st["counts"]["landed"] for st in steps) == 6 + 3
        idle = [sw for sid, sw in sweeps.items()
                if not any(st["parent"] == sid for st in steps)]
        assert len(idle) == 1

    def test_step_counters_on_a_hand_built_grid(self):
        """(b) two decode rows at contexts 21 and 32 and one 5-token
        chunk at positions 12-16 (its first 12 tokens come from the
        prefix cache): rows 7, kv_walked 22 + 33 + 17 (the chunk's five
        rows are one query block of the kernel, which walks its last
        row's keys once), kv_held 22 + 33 + 17. The second call launches
        that step behind the decode step in flight; the third lands it."""
        from paddle_tpu.ops.pallas.paged_attention import walked_keys

        with _fresh(capacity=4096) as tr:
            eng = ServingEngine(_model64(), page_size=4, num_pages=64,
                                max_batch_slots=3, max_model_len=64,
                                token_budget=64, prefix_cache=True)
            rng = np.random.RandomState(5)
            shared = rng.randint(1, 32, (12,))
            eng.add_request(np.append(shared, 7), max_new_tokens=1)
            eng.run()      # its three full pages now sit in the cache
            eng.add_request(rng.randint(1, 32, (20,)), max_new_tokens=8)
            eng.add_request(rng.randint(1, 32, (31,)), max_new_tokens=8)
            eng.step()     # both prompts land whole: decode at 20 and 31
            assert sorted(s.pos for s in eng.slots if s) == [20, 31]
            eng.add_request(np.concatenate([shared, rng.randint(1, 32, (5,))]),
                            max_new_tokens=2)
            eng.step()
            eng.step()
            last = [e for e in tr.events() if e["name"] == "step"][-1]
        assert last["counts"] == {
            "rows": 7, "bucket": eng._grid_tokens(7), "decode_rows": 2,
            "chunk_rows": 5, "draft_rows": 0, "seqs": 3,
            "kv_walked": 22 + 33 + walked_keys(13, 5, eng._query_rows),
            "kv_held": 22 + 33 + 17, "landed": 3, "ahead": 1,
            "discarded": 0, "state_rows": 0, "state_chunk_rows": 0,
            "state_segments": 0, "state_block_rows": 0}
        assert last["parent"] == 0     # no router, no sweep
        assert walked_keys(13, 5, eng._query_rows) == 17

    def test_walk_counter_follows_the_kernels_query_blocks(
            self, monkeypatch):
        """``kv_walked`` counts what the paged kernel walks: a chunk of c
        rows is ceil(c / Q) walks, each its block's last row's keys, and
        a decode row one walk. Q is the kernel module's rule at the pool's
        shapes, cut here so that a 30-token prompt spans four blocks."""
        from paddle_tpu.ops.pallas import paged_attention as pa

        monkeypatch.setattr(pa, "QUERY_BLOCK_VMEM_BYTES", 0)
        with _fresh(capacity=4096) as tr:
            eng = ServingEngine(_model64(), page_size=4, num_pages=64,
                                max_batch_slots=2, max_model_len=64,
                                token_budget=64)
            Q = pa.query_block_rows(eng.pages_per_seq, 1, 4, 16, 4, 1)
            assert eng._query_rows == Q == 8
            rng = np.random.RandomState(7)
            eng.add_request(rng.randint(1, 32, (11,)), max_new_tokens=8)
            eng.step()     # its prompt, whole
            eng.add_request(rng.randint(1, 32, (30,)), max_new_tokens=2)
            eng.step()     # its decode at 11, launched ahead
            eng.step()     # the decode at 12 beside the new prompt
            last = [e for e in tr.events() if e["name"] == "step"][-1]
        c = last["counts"]
        assert (c["decode_rows"], c["chunk_rows"]) == (1, 30)
        # the decode row at position 12; the chunk at 0-29 in blocks of
        # 8, 8, 8 and 6 rows, walking 8, 16, 24 and 30 keys
        assert c["kv_held"] == 13 + 30
        assert c["kv_walked"] == 13 + (8 + 16 + 24 + 30)
        assert c["kv_walked"] == 13 + pa.walked_keys(1, 30, Q)

    def test_spans_lie_on_the_profiler_trace_host_plane(self, tmp_path):
        """(c) under jax.profiler.start_trace the .xplane.pb's host
        plane holds ``sweep``, ``step`` and the five phases, each child
        inside its parent on that clock."""
        import glob

        import jax
        from jax.profiler import ProfileData

        with _fresh(capacity=4096):
            r = Router()
            r.add_model("m", _model64(), replicas=1, page_size=4,
                        num_pages=64, max_batch_slots=2, max_model_len=64)
            r.submit(P5, model="m", max_new_tokens=6)
            r.step()       # compile outside the trace
            jax.profiler.start_trace(str(tmp_path))
            try:
                for _ in range(3):
                    r.step()
            finally:
                jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(
            str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
        wanted = ("sweep", "step") + PHASES
        got = {n: [] for n in wanted}
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name in got:
                        got[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
        assert all(len(got[n]) == 3 for n in wanted), \
            {n: len(v) for n, v in got.items()}
        for i, (s0, s1) in enumerate(sorted(got["step"])):
            w0, w1 = sorted(got["sweep"])[i]
            assert w0 <= s0 and s1 <= w1
            prev = s0
            for name in PHASES:
                c0, c1 = sorted(got[name])[i]
                assert prev <= c0 <= c1 <= s1, (name, prev, c0, c1, s1)
                prev = c1

    def test_disabled_tracer_records_nothing_and_enters_no_annotation(
            self, monkeypatch):
        """(d) disabled: begin/next/end are a flag check — no slot, no
        TraceAnnotation. Enabled: one annotation per span, and a span
        costs under twice the per-event ceiling of the emit guard."""
        entered = []

        class _Ann:
            def __init__(self, name):
                entered.append(name)

            def __exit__(self, *exc):
                pass

        monkeypatch.setattr(ring, "TraceAnnotation", _Ann)
        tr = RequestTracer(capacity=64, enabled=False)
        sp = tr.begin("step", "e")
        ph = tr.next("step.pack", tr.begin("step.plan", "e"))
        tr.end(ph)
        tr.end(sp, (0,) * 9)
        assert sp is None and ph is None
        assert tr.events() == [] and entered == []
        tr.enabled = True
        sp = tr.begin("step", "e")
        ph = tr.next("step.pack", tr.begin("step.plan", "e"))
        tr.end(ph)
        tr.end(sp, (0,) * 9)
        assert entered == ["step", "step.plan", "step.pack"]
        assert [e["name"] for e in tr.events()] \
            == ["step.plan", "step.pack", "step"]
        monkeypatch.undo()

        tr = RequestTracer(capacity=4096)
        N = 20000

        def loop():
            t0 = time.perf_counter()
            for _ in range(N):
                tr.end(tr.begin("step", "e"))
            return time.perf_counter() - t0

        loop()  # warm
        enabled = min(loop() for _ in range(3))
        tr.enabled = False
        disabled = min(loop() for _ in range(3))
        assert enabled / N < 2 * 5e-6   # ~1.3µs measured
        assert disabled / N < 5e-6      # ~0.10µs measured
        assert disabled < enabled

    def test_a_raising_step_closes_its_spans(self):
        """A step body that raises still closes ``step`` and its open
        phase, so the next step's spans are not adopted by a stale one."""
        with _fresh(capacity=1024) as tr:
            eng = ServingEngine(_model(), **_ENGINE_KW)
            eng.add_request(P3, max_new_tokens=2)
            with faults.inject("serving.step", raise_=RuntimeError("x"),
                               times=1, seed=0):
                with pytest.raises(RuntimeError):
                    eng.step()
            eng.run()
            spans = [e for e in _spans(tr.events())
                     if e["req_id"] == eng.engine_id]
        assert tr._open is None
        assert [e["name"] for e in spans[:2]] == ["step.plan", "step"]
        assert "counts" not in spans[1]
        assert all(e["parent"] == 0 for e in spans if e["name"] == "step")

    def test_trace_dump_renders_spans_as_complete_events(self):
        td = _trace_dump_mod()
        step = dict(_ev(2.0, "m/0", 1, "step", arg=0.5), span=2, parent=1,
                    counts={"rows": 3, "bucket": 8})
        evs = [
            dict(_ev(1.6, "m/0", 0, "step.plan", arg=0.1), span=3, parent=2),
            step,
            dict(_ev(2.1, "router", 0, "sweep", arg=0.7), span=1, parent=0),
            _ev(1.0, "a", 0, "req.enqueue"),
        ]
        doc, problems = td.chrome_trace(evs, pid=7)
        assert problems == []
        tracks = {e["tid"]: e["args"]["name"]
                  for e in doc["traceEvents"] if e["ph"] == "M"}
        assert sorted(tracks.values()) == ["req a", "spans m/0",
                                           "spans router"]
        xs = {e["name"]: e for e in doc["traceEvents"]
              if e.get("cat") == "span"}
        assert set(xs) == {"step.plan", "step", "sweep"}
        assert xs["step"]["ts"] == pytest.approx(1.5e6)
        assert xs["step"]["dur"] == pytest.approx(0.5e6)
        assert xs["step"]["args"] == {"key": "m/0", "span": 2, "parent": 1,
                                      "rows": 3, "bucket": 8}
        assert tracks[xs["step"]["tid"]] == "spans m/0"
        assert xs["step"]["tid"] == xs["step.plan"]["tid"] \
            != xs["sweep"]["tid"]


# ─────────────────────────── overhead guard (CI) ───────────────────────────


class TestOverheadGuard:
    def test_disabled_emit_is_a_flag_check(self):
        """Mirror of the metrics disabled-registry guard: emit with
        tracing off must cost within noise of emit with tracing on (it
        does strictly less work), with a generous absolute per-op
        ceiling for loaded CI hosts."""
        tr = RequestTracer(capacity=4096)
        N = 20000

        def loop():
            t0 = time.perf_counter()
            for _ in range(N):
                tr.emit("req.token", "r", arg=1.0)
            return time.perf_counter() - t0

        loop()  # warm
        baseline = min(loop() for _ in range(3))
        tr.enabled = False
        disabled = min(loop() for _ in range(3))
        tr.enabled = True
        assert disabled < baseline * 2.0 + 0.05, (
            f"disabled emit {disabled*1e9/N:.0f}ns/op vs enabled "
            f"{baseline*1e9/N:.0f}ns/op — the disabled path must be a "
            "flag check, not work")
        assert disabled / N < 5e-6  # ~0.15µs measured; 5µs CI ceiling

    @pytest.mark.parametrize("kind", ["emit", "span"])
    def test_enabled_steady_state_is_allocation_free(self, kind):
        """Once the ring has wrapped (every slot's fields already rebound
        under tracemalloc), further emits must not grow the heap — the
        28-byte measured delta over 8192 emits is float/int churn, not
        growth. Bound: under half a KiB per thousand events. A span's
        handle and annotation die with it: the same bound holds."""
        tr = RequestTracer(capacity=1024)
        counts = (1, 2, 3, 4, 5, 6, 7, 8, 9)
        if kind == "emit":
            def one():
                tr.emit("req.token", "warm", arg=1.0)
        else:
            def one():
                tr.end(tr.begin("step", "warm"), counts)
        tracemalloc.start()
        try:
            for _ in range(2048):   # wrap fully UNDER tracemalloc: the
                one()               # live slot values are now traced
            before = tracemalloc.get_traced_memory()[0]
            for _ in range(8192):
                one()
            delta = tracemalloc.get_traced_memory()[0] - before
        finally:
            tracemalloc.stop()
        assert delta < 4096, (
            f"{delta} bytes retained over 8192 {kind}s — the wrapped ring "
            "must mutate slots in place, never allocate")
