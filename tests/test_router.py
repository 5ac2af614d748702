"""paddle_tpu.serving.Router: fleet-scale control plane (ISSUE 6).

Acceptance gates: least-loaded dispatch avoids a loaded engine (ties
round-robin); a degraded engine stops receiving admissions and its
WAITING requests are requeued onto healthy siblings EXACTLY ONCE (no
duplicates, no drops — a request that cannot move retires
deterministically with ``finish_reason="unavailable"``); ``reload()``
across live traffic completes every request, leaves every engine on the
new checkpoint's weights, and never recompiles the unified serving
step (``paddle_tpu_jit_compiles_total{fn="serving_step"}`` pins at the
bucket-set size per engine); multi-model tenancy routes by id with
actionable unknown-id
errors; ``MetricsServer(health_cb=router.health)`` serves aggregate and
``?engine=<id>`` health. The operational twin is tools/chaos_serve.py
scenarios 7-9.
"""
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import faults, metrics
from paddle_tpu.checkpoint import CheckpointManager
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import (CompletionAPI, NoHealthyEngineError,
                                Router)

pytestmark = pytest.mark.serving


def _model(seed=0):
    paddle.seed(seed)
    return LlamaForCausalLM(llama_tiny(
        vocab_size=32, hidden_size=16, num_layers=1, num_heads=1,
        num_key_value_heads=1, max_position_embeddings=32))


# default 30 s stall threshold (a compiling first step must NOT trip it);
# recovery_steps=99 keeps a deliberately tripped watchdog degraded for
# the rest of the test
_ENGINE_KW = dict(page_size=4, max_batch_slots=1,
                  watchdog_recovery_steps=99)

_RNG = np.random.RandomState(7)
P3, P4, P5 = (_RNG.randint(1, 32, (n,)) for n in (3, 4, 5))


def _counter(name, **labels):
    fam = metrics.get_registry().get(name)
    if fam is None:
        return 0.0
    return (fam.labels(**labels) if labels else fam).value


def _trip(engine):
    """Deterministically trip one engine's watchdog: report one
    over-threshold step straight to the state machine (no wall-clock
    sleeps — tools/chaos_serve.py drills the latency-injection route)."""
    engine.watchdog.end_step(engine.watchdog.stall_threshold_s * 2)
    assert engine.health()["status"] == "degraded"


@pytest.fixture(autouse=True)
def _no_leaked_faults():
    faults.reset()
    yield
    faults.reset()


# ───────────────────────────── dispatch ─────────────────────────────


class TestDispatch:
    def test_tie_breaks_round_robin_and_load_steers_away(self):
        r = Router()
        r.add_model("m", _model(), replicas=2, **_ENGINE_KW)
        # idle fleet: scores tie at 0 -> rotation alternates
        a, b = r.select("m"), r.select("m")
        assert {a.engine_id, b.engine_id} == {"m/0", "m/1"}
        # load engine 0: every subsequent pick goes to the idle sibling
        r.engine("m/0").add_request(P5, max_new_tokens=4)
        for _ in range(3):
            assert r.select("m").engine_id == "m/1"
        r.run()

    def test_submit_counts_dispatch_per_engine(self):
        r = Router()
        r.add_model("m", _model(), replicas=2, **_ENGINE_KW)
        # labels collide across tests reusing "m/0" in one process:
        # assert on deltas, not absolutes
        before0 = _counter("paddle_tpu_router_dispatch_total",
                           engine_id="m/0", model_id="m")
        before1 = _counter("paddle_tpu_router_dispatch_total",
                           engine_id="m/1", model_id="m")
        for _ in range(4):  # idle fleet rotates: 2 per engine
            r.submit(P3, model="m", max_new_tokens=1)
            r.run()
        after0 = _counter("paddle_tpu_router_dispatch_total",
                          engine_id="m/0", model_id="m")
        after1 = _counter("paddle_tpu_router_dispatch_total",
                          engine_id="m/1", model_id="m")
        assert after0 - before0 == 2 and after1 - before1 == 2

    def test_unknown_model_and_ambiguous_default_are_actionable(self):
        r = Router()
        r.add_model("a", _model(), **_ENGINE_KW)
        with pytest.raises(ValueError, match=r"unknown model id 'zzz'.*'a'"):
            r.select("zzz")
        r.add_model("b", _model(), **_ENGINE_KW)
        with pytest.raises(ValueError, match=r"model= is required"):
            r.select(None)

    def test_no_healthy_engine_raises(self):
        r = Router()
        r.add_model("m", _model(), **_ENGINE_KW)
        r.mark_down("m/0")
        with pytest.raises(NoHealthyEngineError, match=r"no healthy.*'m'"):
            r.select("m")
        r.undrain("m/0")
        assert r.select("m").engine_id == "m/0"


# ──────────────────────── multi-model tenancy ────────────────────────


class TestTenancy:
    def test_completion_api_model_field_routes(self):
        r = Router()
        r.add_model("tiny-a", _model(0), **_ENGINE_KW)
        r.add_model("tiny-b", _model(1), **_ENGINE_KW)
        api = CompletionAPI(r)
        chunks = []
        ra = api.create_completion(P4, max_tokens=3, model="tiny-a",
                                   stream_cb=chunks.append)
        rb = api.create_completion(P4, max_tokens=3, model="tiny-b")
        assert ra["model"] == "tiny-a" and rb["model"] == "tiny-b"
        # streamed chunks carry the ROUTED tenant, matching the response
        assert {c["model"] for c in chunks} == {"tiny-a"}
        assert ra["choices"][0]["finish_reason"] == "length"
        # different weights -> (deterministically seeded) routing is real:
        # the two tenants answer from different models
        assert (ra["choices"][0]["token_ids"]
                != rb["choices"][0]["token_ids"])
        with pytest.raises(ValueError, match=r"unknown model id 'nope'"):
            api.create_completion(P4, max_tokens=3, model="nope")
        with pytest.raises(ValueError, match=r"model= is required"):
            api.create_completion(P4, max_tokens=3)

    def test_engine_backed_api_rejects_foreign_model(self):
        from paddle_tpu.serving import ServingEngine

        eng = ServingEngine(_model(), page_size=4, max_batch_slots=1)
        api = CompletionAPI(eng, model_name="solo")
        assert api.create_completion(P3, max_tokens=2,
                                     model="solo")["model"] == "solo"
        with pytest.raises(ValueError, match=r"serves only 'solo'"):
            api.create_completion(P3, max_tokens=2, model="other")


# ──────────────────── health gating + auto-drain ────────────────────


class TestHealthGate:
    def test_degraded_engine_loses_admissions_waiting_work_moves_once(self):
        r = Router()
        r.add_model("m", _model(), replicas=2, **_ENGINE_KW)
        e0 = r.engine("m/0")
        running = e0.add_request(P5, max_new_tokens=6)
        e0.step()  # running now occupies the only slot
        q1 = e0.add_request(P3, max_new_tokens=2)
        q2 = e0.add_request(P4, max_new_tokens=2)
        moved_before = _counter("paddle_tpu_router_requeued_total")
        _trip(e0)
        r.step()  # health refresh: m/0 -> degraded, waiting work moves
        assert r.states()["m/0"] == "degraded"
        assert e0.scheduler.queue_depth == 0  # waiting work left m/0
        assert (_counter("paddle_tpu_router_requeued_total")
                == moved_before + 2)
        assert r.select("m").engine_id == "m/1"  # gated out of admission
        outs = r.run()
        # exactly once, no drops: all three requests complete normally
        # (the in-flight one finishes on the degraded engine itself)
        assert sorted(outs) == sorted([running, q1, q2])
        assert {o.finish_reason for o in outs.values()} == {"length"}

    def test_requeue_impossible_retires_unavailable_exactly_once(self):
        r = Router()
        r.add_model("m", _model(), **_ENGINE_KW)  # NO sibling
        e0 = r.engine("m/0")
        running = e0.add_request(P5, max_new_tokens=4)
        e0.step()
        q1 = e0.add_request(P3, max_new_tokens=2)
        unplaceable_before = _counter("paddle_tpu_router_unplaceable_total")
        unavailable_before = _counter("paddle_tpu_serving_unavailable_total")
        _trip(e0)
        outs = r.run()
        assert outs[q1].finish_reason == "unavailable"
        assert outs[running].finish_reason == "length"
        assert len(outs) == 2
        assert (_counter("paddle_tpu_router_unplaceable_total")
                == unplaceable_before + 1)
        assert (_counter("paddle_tpu_serving_unavailable_total")
                == unavailable_before + 1)

    def test_moved_request_never_moves_twice(self):
        """Second failure after a requeue retires the request instead of
        bouncing it around the fleet — the exactly-once guarantee."""
        r = Router()
        r.add_model("m", _model(), replicas=2, **_ENGINE_KW)
        e0, e1 = r.engine("m/0"), r.engine("m/1")
        b0 = e0.add_request(P5, max_new_tokens=16)
        b1 = e1.add_request(P4, max_new_tokens=16)
        e0.step()
        e1.step()  # both single slots now busy with long decodes
        q = e0.add_request(P3, max_new_tokens=2)
        _trip(e0)
        r.step()  # q moves m/0 -> m/1's queue (its only move)
        moved = _counter("paddle_tpu_router_requeued_total")
        assert e1.scheduler.queue_depth == 1
        # m/1 degrades while q still waits behind b1; m/0 cannot recover
        # (recovery_steps=99) -> q has nowhere left to go
        _trip(e1)
        outs = r.run()
        assert outs[q].finish_reason == "unavailable"
        assert outs[b0].finish_reason == "length"
        assert outs[b1].finish_reason == "length"
        assert _counter("paddle_tpu_router_requeued_total") == moved

    def test_nan_poisoned_stream_fails_over_without_dupes_or_drops(self):
        """The ISSUE drill: NaN-poison an engine mid-stream, degrade it —
        the victim quarantines, waiting work completes elsewhere, every
        req_id appears exactly once."""
        r = Router()
        r.add_model("m", _model(), replicas=2, **_ENGINE_KW)
        e0 = r.engine("m/0")
        victim = e0.add_request(P5, max_new_tokens=8)
        e0.step()
        queued = [e0.add_request(P3, max_new_tokens=2),
                  e0.add_request(P4, max_new_tokens=3)]
        e0.pool.poison_seq(victim)
        _trip(e0)
        outs = r.run()
        assert outs[victim].finish_reason == "nan"
        assert [outs[q].finish_reason for q in queued] == ["length"] * 2
        assert len(outs) == 3  # exactly once each, nothing extra
        assert e0.pool.used_pages == 0

    def test_mark_down_migrates_in_flight(self):
        """mark_down no longer kills in-flight work: it migrates by token
        journal to the sibling and completes token-identically there."""
        # reference: the same request uninterrupted on a lone engine
        from paddle_tpu.serving import ServingEngine

        ref_eng = ServingEngine(_model(), **_ENGINE_KW)
        ref_id = ref_eng.add_request(P5, max_new_tokens=8)
        ref = ref_eng.run()[ref_id].token_ids

        r = Router()
        r.add_model("m", _model(), replicas=2, **_ENGINE_KW)
        e0 = r.engine("m/0")
        running = e0.add_request(P5, max_new_tokens=8)
        e0.step()
        e0.step()  # a few tokens journaled before the engine goes down
        q = e0.add_request(P3, max_new_tokens=2)
        migrated_before = _counter("paddle_tpu_router_migrated_total")
        r.mark_down("m/0")
        assert r.states()["m/0"] == "down"
        outs = r.run()
        assert outs[running].finish_reason == "length"  # finished on m/1
        assert list(outs[running].token_ids) == list(ref)  # token-identical
        assert outs[q].finish_reason == "length"  # moved to m/1
        assert (_counter("paddle_tpu_router_migrated_total")
                == migrated_before + 1)
        assert e0.pool.used_pages == 0
        assert r._requeued == set()  # marks reaped after the drain


# ──────────────── crash containment + in-flight migration ────────────────


class TestCrashContainment:
    """ISSUE 7 tentpole: an engine dying mid-decode is contained by
    router.step() (never propagates), and its in-flight requests migrate
    by token journal to a sibling that continues each stream
    token-identically with exactly-once stream chunks."""

    def _ref_tokens(self, prompt, n, seed, temperature):
        """The same request decoded uninterrupted on a lone engine — the
        determinism contract makes this THE reference for any migrated
        run of the same (prompt, seed, temperature)."""
        from paddle_tpu.serving import ServingEngine

        eng = ServingEngine(_model(), **_ENGINE_KW)
        rid = eng.add_request(prompt, max_new_tokens=n, seed=seed,
                              temperature=temperature)
        return list(eng.run()[rid].token_ids)

    def test_step_crash_contained_and_migrated_token_identically(self):
        ref = self._ref_tokens(P5, 8, seed=3, temperature=0.8)
        r = Router()
        r.add_model("m", _model(), replicas=2, **_ENGINE_KW)
        e0 = r.engine("m/0")
        chunks = []  # 4-arg callback: receives the monotone seq numbers
        rid = e0.add_request(
            P5, max_new_tokens=8, temperature=0.8, seed=3,
            stream_cb=lambda req_id, tok, fin, seq: chunks.append(
                (seq, tok)))
        e0.step()
        e0.step()  # a couple of tokens journaled before the crash
        crash0 = _counter("paddle_tpu_router_engine_crash_total",
                          engine_id="m/0", model_id="m")
        moved0 = _counter("paddle_tpu_router_migrated_total")
        with faults.inject("router.engine_step",
                           raise_=RuntimeError("chip died"), times=1):
            r.step()  # contained: must NOT raise
        assert r.states()["m/0"] == "down"
        assert (_counter("paddle_tpu_router_engine_crash_total",
                         engine_id="m/0", model_id="m") == crash0 + 1)
        outs = r.run()
        assert outs[rid].finish_reason == "length"
        assert list(outs[rid].token_ids) == ref  # bit-identical stream
        assert (_counter("paddle_tpu_router_migrated_total")
                == moved0 + 1)
        # exactly-once streaming: seqs 0..7 each exactly once, in order,
        # carrying exactly the reference tokens (terminal chunk: seq=8)
        tok_chunks = [c for c in chunks if c[1] is not None]
        assert [s for s, _ in tok_chunks] == list(range(8))
        assert [t for _, t in tok_chunks] == ref
        assert chunks[-1] == (8, None)
        assert r._requeued == set()  # move-once marks reaped after drain
        assert "chip died" in r.health(engine="m/0")["last_error"]

    def test_unplaceable_inflight_retires_unavailable_with_tokens(self):
        r = Router()
        r.add_model("m", _model(), **_ENGINE_KW)  # NO sibling
        e0 = r.engine("m/0")
        rid = e0.add_request(P5, max_new_tokens=8)
        e0.step()
        e0.step()
        journal = list(e0.slots[0].gen)  # tokens generated so far
        un0 = _counter("paddle_tpu_router_unplaceable_total")
        with faults.inject("router.engine_step",
                           raise_=RuntimeError("dead"), times=1):
            r.step()
        outs = r.run()
        # the already-streamed tokens deliver with the terminal output
        assert outs[rid].finish_reason == "unavailable"
        assert list(outs[rid].token_ids) == journal
        assert (_counter("paddle_tpu_router_unplaceable_total")
                == un0 + 1)
        assert r._requeued == set()

    def test_migrated_inflight_never_moves_twice(self):
        """Second engine death after a migration retires the request
        (with its full journal) instead of bouncing it around the fleet
        — the move-once discipline covers migration too."""
        r = Router()
        r.add_model("m", _model(), replicas=3, **_ENGINE_KW)
        e0 = r.engine("m/0")
        rid = e0.add_request(P5, max_new_tokens=16)
        e0.step()
        moved0 = _counter("paddle_tpu_router_migrated_total")
        with faults.inject("router.engine_step",
                           raise_=RuntimeError("first death"), times=1):
            r.step()  # e0 dies; rid migrates (once) to a sibling
        assert (_counter("paddle_tpu_router_migrated_total")
                == moved0 + 1)
        adoptive = next(h for h in r._model_handles("m")
                        if h.engine.has_work)
        adoptive.engine.step()  # rid decoding IN-FLIGHT on the adoptive
        n_gen = len(adoptive.engine.slots[0].gen)
        assert n_gen >= 1
        with faults.inject("router.engine_step",
                           raise_=RuntimeError("second death"), times=1):
            r.step()  # adoptive dies; a healthy sibling exists, but the
            #           request already used its one move
        outs = r.run()
        assert outs[rid].finish_reason == "unavailable"
        assert len(outs[rid].token_ids) >= n_gen  # full journal delivered
        assert (_counter("paddle_tpu_router_migrated_total")
                == moved0 + 1)  # no second migration
        assert r._requeued == set()

    def test_mark_down_on_dead_engine_never_raises(self):
        """Satellite: an engine too dead to cooperate — every control
        surface raising — must still be markable down (the guard the old
        in-flight cancel loop lacked). Its requests are SCRAPED from the
        host-side state the broken methods sat on, so they still migrate
        instead of silently vanishing."""
        r = Router()
        r.add_model("m", _model(), replicas=2, **_ENGINE_KW)
        e0 = r.engine("m/0")
        running = e0.add_request(P5, max_new_tokens=4)
        e0.step()
        waiting = e0.add_request(P3, max_new_tokens=2)

        def boom(*a, **k):
            raise RuntimeError("engine is gone")

        e0.steal_queued = boom
        e0.export_inflight = boom
        e0.cancel = boom
        e0.retire_queued = boom
        e0.step = boom
        r.mark_down("m/0")  # must not throw
        assert r.states()["m/0"] == "down"
        outs = r.run()  # the fleet keeps serving — and recovered BOTH
        assert outs[running].finish_reason == "length"
        assert outs[waiting].finish_reason == "length"

    def test_raising_health_probe_is_contained(self):
        """health()/has_work raising must not kill the fleet loop: the
        broken engine gates down (crash-counted) and its work moves."""
        r = Router()
        r.add_model("m", _model(), replicas=2, **_ENGINE_KW)
        e0 = r.engine("m/0")
        rid = e0.add_request(P5, max_new_tokens=4)
        e0.step()

        def boom(*a, **k):
            raise RuntimeError("probe exploded")

        e0.health = boom
        crash0 = _counter("paddle_tpu_router_engine_crash_total",
                          engine_id="m/0", model_id="m")
        r.step()  # must not raise
        assert r.states()["m/0"] == "down"
        assert (_counter("paddle_tpu_router_engine_crash_total",
                         engine_id="m/0", model_id="m") == crash0 + 1)
        outs = r.run()
        assert outs[rid].finish_reason == "length"  # migrated, finished
        assert "probe exploded" in r.health(engine="m/0")["last_error"]

    def test_requeued_marks_cleared_without_router_visible_output(self):
        """Satellite regression: a moved request that retires without its
        output ever passing router.run() (cancelled on the adoptive
        engine, drained via engine.run() directly) must not leak its
        move-once mark forever."""
        r = Router()
        r.add_model("m", _model(), replicas=2, **_ENGINE_KW)
        e0, e1 = r.engine("m/0"), r.engine("m/1")
        b1 = e1.add_request(P4, max_new_tokens=8)
        e1.step()  # e1's only slot busy
        q = e0.add_request(P3, max_new_tokens=2)
        _trip(e0)
        r.step()  # q requeues m/0 -> m/1 and takes its move-once mark
        assert q in r._requeued
        e1.cancel(q)  # retired on the ADOPTIVE engine...
        e1.run()      # ...and drained engine-side, bypassing router.run
        r.run()
        assert r._requeued == set()  # the mark did not leak

    def test_inflight_migrates_before_waiting_under_tight_capacity(self):
        """Evacuation order: the in-flight request (sunk tokens, live
        stream) takes the sibling's last queue seat; the never-started
        waiting request is the one that retires unavailable."""
        r = Router()
        r.add_model("m", _model(), replicas=2, max_queue=1, **_ENGINE_KW)
        e0 = r.engine("m/0")
        running = e0.add_request(P5, max_new_tokens=8)
        e0.step()  # running mid-decode in e0's only slot
        waiting = e0.add_request(P3, max_new_tokens=2)
        r.mark_down("m/0")
        outs = r.run()
        assert outs[running].finish_reason == "length"  # kept its seat
        assert outs[waiting].finish_reason == "unavailable"

    def test_marks_reaped_in_step_driven_loop_without_run(self):
        """A long-lived server driving the fleet with step() — never
        run() — must not leak move-once marks after a failover: step()
        reaps marks of moved requests that retired on their adoptive
        engine."""
        r = Router()
        r.add_model("m", _model(), replicas=2, **_ENGINE_KW)
        e0 = r.engine("m/0")
        rid = e0.add_request(P5, max_new_tokens=6)
        e0.step()
        with faults.inject("router.engine_step",
                           raise_=RuntimeError("died"), times=1):
            r.step()  # rid migrates and takes its move-once mark
        assert rid in r._requeued
        for _ in range(40):  # step-driven drain: run() never called
            if not r.has_work:
                break
            r.step()
        outs = {}
        for eng in r.engines("m"):
            outs.update(eng.take_outputs())
        assert outs[rid].finish_reason == "length"
        assert r._requeued == set()  # reaped without run()

    def test_unavailable_inflight_on_broken_engine_synthesizes_output(self):
        """Even when the source engine's emit path is dead, the caller
        still gets its terminal output exactly once (router stash)."""
        r = Router()
        r.add_model("m", _model(), **_ENGINE_KW)  # no sibling
        e0 = r.engine("m/0")
        rid = e0.add_request(P5, max_new_tokens=8)
        e0.step()
        journal = list(e0.slots[0].gen)

        def boom(*a, **k):
            raise RuntimeError("emit path dead")

        e0.retire_queued = boom
        chunks = []
        e0.slots[0].req.stream_cb = (
            lambda r_, tok, fin, seq: chunks.append((seq, tok, fin)))
        r.mark_down("m/0")
        outs = r.run()
        assert outs[rid].finish_reason == "unavailable"
        assert list(outs[rid].token_ids) == journal
        # the streaming client still gets its terminal chunk
        assert chunks[-1] == (len(journal), None, "unavailable")


# ─────────────────────────── /healthz wiring ───────────────────────────


class TestHealthz:
    def test_aggregate_503_only_when_a_model_is_dark(self):
        r = Router()
        r.add_model("m", _model(), replicas=2, **_ENGINE_KW)
        with metrics.MetricsServer(health_cb=r.health, port=0) as srv:
            with urllib.request.urlopen(f"{srv.url}/healthz") as resp:
                assert resp.status == 200
            _trip(r.engine("m/0"))
            r.step()
            # one degraded replica: sibling covers -> still 200
            with urllib.request.urlopen(f"{srv.url}/healthz") as resp:
                body = json.loads(resp.read())
                assert resp.status == 200
                assert body["models"]["m"]["healthy"] == 1
            # per-engine view: the degraded one reports 503
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{srv.url}/healthz?engine=m/0")
            assert ei.value.code == 503
            assert json.loads(ei.value.read())["state"] == "degraded"
            with urllib.request.urlopen(
                    f"{srv.url}/healthz?engine=m/1") as resp:
                assert resp.status == 200
            # whole model dark -> aggregate 503
            r.mark_down("m/1")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{srv.url}/healthz")
            assert ei.value.code == 503
            assert json.loads(ei.value.read())["status"] == "degraded"
            # unknown engine id: non-ok and names the known ids
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{srv.url}/healthz?engine=ghost")
            assert ei.value.code == 503
            assert "m/0" in json.loads(ei.value.read())["known"]

    def test_engine_health_cb_ignores_engine_query(self):
        """A health_cb without the engine= keyword (plain engine.health)
        keeps working when a prober appends ?engine=."""
        from paddle_tpu.serving import ServingEngine

        eng = ServingEngine(_model(), page_size=4, max_batch_slots=1)
        with metrics.MetricsServer(health_cb=eng.health, port=0) as srv:
            with urllib.request.urlopen(
                    f"{srv.url}/healthz?engine=whatever") as resp:
                assert resp.status == 200


# ───────────────────────── rolling weight reload ─────────────────────────


class TestReload:
    def _ckpt(self, tmp_path, seed=1):
        donor = _model(seed)
        sd = donor.state_dict()
        CheckpointManager(str(tmp_path), max_to_keep=None).save(
            7, {"model": sd})
        return sd

    def test_rolling_reload_across_live_traffic(self, tmp_path):
        sd = self._ckpt(tmp_path)
        # one model INSTANCE per replica: true rolling version isolation
        r = Router()
        r.add_model("m", [_model(0), _model(0)], page_size=4,
                    max_batch_slots=1)
        live = [r.submit(P5, model="m", max_new_tokens=6)
                for _ in range(4)]
        ok_before = _counter("paddle_tpu_router_reloads_total", result="ok")
        summary = r.reload(str(tmp_path))
        assert summary["step"] == 7
        assert [e["result"] for e in summary["engines"]] == ["ok", "ok"]
        outs = r.run()
        # every live request completed exactly once, none dropped
        assert sorted(k for k in outs if k in live) == sorted(live)
        assert all(outs[k].finish_reason == "length" for k in live)
        # all engines serve the checkpoint's weights now
        for eng in r.engines("m"):
            got = eng.model.state_dict()
            for k, v in sd.items():
                np.testing.assert_array_equal(np.asarray(got[k].numpy()),
                                              np.asarray(v.numpy()))
            # in-place restore: the compiled step survived the push
            counts = eng.compile_counts()
            assert counts["step"] == counts["step_buckets"]
        assert r.states() == {"m/0": "healthy", "m/1": "healthy"}
        assert all(h.weights_step == 7
                   for h in r._model_handles("m"))
        assert (_counter("paddle_tpu_router_reloads_total", result="ok")
                == ok_before + 2)

    def test_reload_flushes_stale_prefix_cache(self, tmp_path):
        """The radix prefix cache holds KV computed under the OLD
        weights: reload() must flush it, or a post-push warm hit would
        mix stale prefix KV with new-weight suffix compute — the same
        prompt after the push must match a fresh engine running the
        checkpoint's weights with no cache at all."""
        self._ckpt(tmp_path)
        r = Router()
        r.add_model("m", _model(0), replicas=1, page_size=4,
                    max_batch_slots=1)
        eng = r.engine("m/0")
        prompt = np.concatenate([P5, P4, P3])  # 12 tokens: 3 full pages
        rid = r.submit(prompt, model="m", max_new_tokens=4,
                       temperature=0.9, seed=3)
        r.run()
        assert len(eng.prefix_cache) > 0  # old-weight KV is indexed
        r.reload(str(tmp_path))
        assert len(eng.prefix_cache) == 0  # flushed with the weights
        # oracle: cache-off engine on the checkpoint's weights
        from paddle_tpu.serving import ServingEngine

        oracle = ServingEngine(_model(1), page_size=4, max_batch_slots=1,
                               prefix_cache=False)
        want_id = oracle.add_request(prompt, max_new_tokens=4,
                                     temperature=0.9, seed=3)
        want = list(oracle.run()[want_id].token_ids)
        rid2 = r.submit(prompt, model="m", max_new_tokens=4,
                        temperature=0.9, seed=3)
        got = list(r.run()[rid2].token_ids)
        assert got == want and rid2 != rid

    def test_reload_requires_model_on_multi_tenant_router(self, tmp_path):
        """A checkpoint belongs to one architecture: reload() without
        model= must refuse on a multi-model router instead of pushing the
        weights into every tenant's engines."""
        self._ckpt(tmp_path)
        r = Router()
        r.add_model("a", _model(), **_ENGINE_KW)
        r.add_model("b", _model(), **_ENGINE_KW)
        with pytest.raises(ValueError, match=r"model= is required"):
            r.reload(str(tmp_path))
        assert r.states() == {"a/0": "healthy", "b/0": "healthy"}

    def test_reload_single_engine_finishes_own_queue_first(self, tmp_path):
        self._ckpt(tmp_path)
        r = Router()
        r.add_model("m", _model(), **_ENGINE_KW)
        rid = r.submit(P4, model="m", max_new_tokens=3)
        r.reload(str(tmp_path))
        outs = r.run()
        assert outs[rid].finish_reason == "length"  # not "unavailable"

    def test_reload_survives_engine_crash_during_drain(self, tmp_path):
        """A reload whose engine dies mid-drain — too dead even to
        evacuate — must return an error result, not spin forever on
        has_work for an engine step() will never touch again."""
        self._ckpt(tmp_path)
        r = Router()
        r.add_model("m", _model(), **_ENGINE_KW)
        e0 = r.engine("m/0")
        e0.add_request(P4, max_new_tokens=3)

        def boom(*a, **k):
            raise RuntimeError("dead mid-drain")

        e0.step = boom
        e0.steal_queued = boom
        e0.export_inflight = boom
        summary = r.reload(str(tmp_path))
        assert summary["engines"][0]["result"] == "error"
        assert "dead mid-drain" in summary["engines"][0]["error"]
        assert r.states()["m/0"] == "down"

    def test_reload_survives_raising_has_work_probe(self, tmp_path):
        """Even the drain loop's has_work PROBE raising must not escape
        reload() or leave the engine stuck DRAINING: the probe is
        contained (engine down) and the summary reports the error."""
        self._ckpt(tmp_path)
        r = Router()
        r.add_model("m", _model(), **_ENGINE_KW)
        e0 = r.engine("m/0")

        class _Trashed:
            def __getattr__(self, name):
                raise RuntimeError("scheduler state trashed")

        e0.scheduler = _Trashed()  # has_work now raises
        summary = r.reload(str(tmp_path))
        assert summary["engines"][0]["result"] == "error"
        assert "trashed" in summary["engines"][0]["error"]
        assert r.states()["m/0"] == "down"

    def test_bad_checkpoint_canary_gates_engine_down(self, tmp_path):
        donor = _model(1)
        sd = donor.state_dict()
        poisoned = {k: (paddle.to_tensor(
            np.full(v.numpy().shape, np.nan, np.float32))
            if i == 0 else v)
            for i, (k, v) in enumerate(sd.items())}
        CheckpointManager(str(tmp_path), max_to_keep=None).save(
            3, {"model": poisoned})
        r = Router()
        r.add_model("m", _model(), **_ENGINE_KW)
        err_before = _counter("paddle_tpu_router_reloads_total",
                              result="error")
        summary = r.reload(str(tmp_path))
        assert summary["engines"][0]["result"] == "error"
        assert summary["engines"][0]["canary_finish_reason"] == "nan"
        assert r.states()["m/0"] == "down"
        assert (_counter("paddle_tpu_router_reloads_total", result="error")
                == err_before + 1)


# ─────────────── rotation + label coverage (ex-EnginePool shim) ───────────────


class TestRotationAndLabels:
    """The EnginePool shim is gone (ISSUE 16); its remaining guarantees
    — bounded round-robin rotation, indexable engines, per-engine metric
    labels — are asserted on the Router surface directly."""

    def test_engine_pool_shim_is_deleted(self):
        import paddle_tpu.serving as serving
        assert not hasattr(serving, "EnginePool")
        assert not hasattr(serving.api, "EnginePool")

    def test_modular_round_robin_tie_break(self):
        router = Router()
        router.add_model("default", _model(), replicas=2, page_size=4,
                         max_batch_slots=1)
        # an idle fleet is an exact load tie: the cursor rotates and
        # stays MODULAR (never an unbounded count)
        picks = [router.select().engine_id for _ in range(4)]
        assert picks == ["default/0", "default/1",
                         "default/0", "default/1"]
        assert router._rr["default"] in (0, 1)
        assert len(router) == 2
        # indexable engines survived the shim: engines() is ordered
        engines = router.engines("default")
        assert engines[0] is router.engine("default/0")
        assert router.health()["status"] == "ok"

    def test_serving_series_carry_engine_and_model_labels(self):
        router = Router()
        router.add_model("default", _model(), replicas=2, page_size=4,
                         max_batch_slots=1)
        rid = router.submit(P3, max_new_tokens=2)
        outs = router.run()
        assert outs[rid].finish_reason == "length"
        snap = metrics.get_registry().snapshot()
        labels = [s["labels"] for s in
                  snap["paddle_tpu_serving_ttft_seconds"]["series"]]
        assert {"engine_id": "default/0", "model_id": "default"} in labels \
            or {"engine_id": "default/1", "model_id": "default"} in labels
        states = {tuple(sorted(s["labels"].items())): s["value"] for s in
                  snap["paddle_tpu_router_engine_state"]["series"]}
        assert states[(("engine_id", "default/0"),
                       ("model_id", "default"))] == 0.0


class TestPipelinedFleet:
    def test_each_engine_runs_a_step_ahead_streams_match_dense(self):
        """Two engines behind the router each keep their own step in
        flight: a sweep launches each engine's next step before it lands
        the one in flight, and every stream is token-identical to dense
        generate()."""
        from paddle_tpu.serving import tracing

        tr = tracing.RequestTracer(capacity=65536)
        old = tracing.set_tracer(tr)
        try:
            model = _model()
            r = Router()
            r.add_model("m", model, replicas=2, page_size=4,
                        max_batch_slots=2)
            prompts = [P3, P4, P5, P4[::-1]]
            rids = [r.submit(p, model="m", max_new_tokens=6)
                    for p in prompts]
            outs = r.run()
            evs = tr.events()
        finally:
            tracing.set_tracer(old)
        for rid, p in zip(rids, prompts):
            dense = model.generate(paddle.to_tensor(np.asarray(p)[None, :]),
                                   max_new_tokens=6, temperature=0.0)
            assert list(outs[rid].token_ids) == list(
                np.asarray(dense.numpy())[0, len(p):])
        for eid in ("m/0", "m/1"):
            ahead = [e["counts"]["ahead"] for e in evs
                     if e["name"] == "step" and e["req_id"] == eid
                     and "counts" in e]
            assert len(ahead) >= 6 and sum(ahead) >= len(ahead) - 2
            eng = r.engine(eid)
            assert eng.pool.used_pages == 0 and not eng._flight
            c = eng.compile_counts()
            assert c["step"] == c["step_buckets"]
