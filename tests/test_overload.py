"""serving.overload: overload as a first-class failure mode (ISSUE 19).

Two lanes, mirroring the autoscaler suite in test_loadgen.py:

- **Fake lane** (no jax): the brownout ladder's hysteresis against a
  host-only router/engine double — no-flap inside the band, climb/
  restore trajectories with cooldown, the level -> action mapping, the
  deadline-aware admission-gate math, and the ONE-estimator agreement
  between ``BackpressureError.retry_after_s`` and the gate's shed
  prediction (the regression that keeps the retry hint honest).
- **Real-engine lane** (CPU jax, test_serving scale): queued-expiry
  exactness (``"expired"`` is never-admitted work ONLY; journaled
  queued work still retires ``"timeout"``), and the determinism
  contract under brownout PREEMPTION — a batch-tier stream journaled
  out of its slot mid-decode and restored after de-escalation ends
  bit-identical to an undisturbed run, stream chunks exactly-once,
  with the compile surface untouched.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import metrics
from paddle_tpu.serving import Request
from paddle_tpu.serving.overload import (AdmissionShedError, DrainEstimator,
                                         LEVELS, OverloadConfig,
                                         OverloadController, RetryBudget)
from paddle_tpu.serving.scheduler import BackpressureError, FCFSScheduler

pytestmark = pytest.mark.serving


def _counter(name, **labels):
    fam = metrics.get_registry().get(name)
    if fam is None:
        return 0.0
    return (fam.labels(**labels) if labels else fam).value


# ─────────────────────────── host-only doubles ───────────────────────────


class _FakeTrace:
    def __init__(self):
        self.events = []

    def emit(self, name, req_id, arg=0.0, label="", **kw):
        self.events.append((name, req_id, arg, label))


class _FakeSched:
    def __init__(self):
        self.queue_depth = 0
        self.waiting = []


class _FakeEngine:
    """The signal surface the controller reads (queue_depth,
    avg_step_s) plus the trace sink its shed/level emits hit."""

    def __init__(self):
        self.scheduler = _FakeSched()
        self.avg_step_s = 0.05
        self._trace = _FakeTrace()
        self._overload = None


class _FakeRouter:
    """Topology double (test_loadgen autoscaler idiom): real
    EngineHandle states around fake engines, so ``signal()`` sees the
    same health gating the live router applies."""

    def __init__(self, n=1):
        from paddle_tpu.serving.router import EngineHandle
        self._hs = [EngineHandle(_FakeEngine(), f"m/{i}", "m")
                    for i in range(n)]

    def _resolve_model(self, model):
        return "m"

    def handles(self, model=None):
        return list(self._hs)

    def set_depth(self, d, i=None):
        for j, h in enumerate(self._hs):
            if i is None or i == j:
                h.engine.scheduler.queue_depth = d


def _ctl(router, **kw):
    kw.setdefault("hot_backlog_s", 1.0)
    kw.setdefault("cold_backlog_s", 0.25)
    kw.setdefault("hot_steps", 2)
    kw.setdefault("cold_steps", 3)
    kw.setdefault("cooldown_steps", 2)
    return OverloadController(router, config=OverloadConfig(**kw))


# ──────────────────────────── shared estimator ────────────────────────────


class TestDrainEstimator:
    def test_prediction_is_depth_times_ewma_with_floor(self):
        est = DrainEstimator(floor_s=0.05)
        assert est.predict_wait_s(0, 0.1) == 0.05       # floor
        assert est.predict_wait_s(8, 0.1) == pytest.approx(0.8)
        eng = _FakeEngine()
        eng.scheduler.queue_depth = 6
        eng.avg_step_s = 0.2
        assert est.for_engine(eng) == pytest.approx(1.2)

    def test_floor_must_be_positive(self):
        with pytest.raises(ValueError):
            DrainEstimator(floor_s=0.0)

    def test_gate_and_backpressure_hint_agree(self):
        """THE satellite regression: the shed's retry_after_s and the
        honest backpressure hint come from one estimator — identical
        numbers for identical engine state, by construction."""
        r = _FakeRouter()
        ctl = _ctl(r)
        eng = r.handles()[0].engine
        eng.scheduler.queue_depth = 7
        eng.avg_step_s = 0.09
        predicted = ctl.estimator.for_engine(eng)
        req = Request(prompt=np.arange(1, 4), deadline_s=0.1)
        with pytest.raises(AdmissionShedError) as ei:
            ctl.admission_check(eng, req)
        assert ei.value.retry_after_s == predicted == pytest.approx(0.63)
        # and the engine-side hint delegates to the same math
        assert (DrainEstimator(ctl.config.floor_s).for_engine(eng)
                == predicted)


class TestRetryBudget:
    def test_take_refill_and_dry_bucket(self):
        b = RetryBudget(capacity=2.0, refill_per_step=0.5)
        assert b.tokens("m") == 2.0                 # full until touched
        assert b.try_take("m") and b.try_take("m")
        assert not b.try_take("m")                  # dry: no spend, False
        assert b.tokens("m") == 0.0
        b.refill()
        assert not b.try_take("m")                  # 0.5 < 1 token
        b.refill()
        assert b.try_take("m")                      # 1.0 spends
        for _ in range(10):
            b.refill()
        assert b.tokens("m") == 2.0                 # capped at capacity

    def test_validation(self):
        with pytest.raises(ValueError):
            RetryBudget(capacity=0.0)
        with pytest.raises(ValueError):
            RetryBudget(refill_per_step=-1.0)


class TestOverloadConfig:
    def test_band_must_be_a_band(self):
        with pytest.raises(ValueError):
            OverloadConfig(hot_backlog_s=0.2, cold_backlog_s=0.2)

    def test_knob_validation(self):
        with pytest.raises(ValueError):
            OverloadConfig(hot_steps=0)
        with pytest.raises(ValueError):
            OverloadConfig(cooldown_steps=-1)
        with pytest.raises(ValueError):
            OverloadConfig(max_level=0)
        with pytest.raises(ValueError):
            OverloadConfig(batch_chunk_cap=0)
        with pytest.raises(ValueError):
            OverloadConfig(deadline_slack=0.0)


# ──────────────────────────── ladder hysteresis ────────────────────────────


class TestLadderHysteresis:
    def test_signal_inside_band_never_moves_the_ladder(self):
        """No-flap: a noisy signal parked INSIDE the hysteresis band
        (cold <= sig <= hot) makes every decision 'steady'."""
        r = _FakeRouter()
        ctl = _ctl(r)  # band (0.25, 1.0); depth x 0.05s
        for depth in (8, 12, 19, 6, 15, 8, 19, 12) * 4:
            r.set_depth(depth)       # 0.3 .. 0.95 s — inside the band
            assert ctl.observe() == "steady"
        assert ctl.level == 0 and ctl.events == []

    def test_climb_needs_consecutive_hot_and_cooldown_gates_next(self):
        r = _FakeRouter()
        ctl = _ctl(r)  # hot_steps=2, cooldown_steps=2
        r.set_depth(100)                       # 5 s >> hot
        assert ctl.observe() == "steady"       # 1st hot: not yet
        assert ctl.observe() == "escalate"     # 2nd: level 1
        assert ctl.level == 1
        assert ctl.observe() == "cooldown"     # sit out 2 obs
        assert ctl.observe() == "cooldown"
        # still-hot ticks count THROUGH the cooldown (it gates the
        # move, not the evidence), so a persistent storm climbs on the
        # first post-cooldown tick
        assert ctl.observe() == "escalate"
        assert ctl.level == 2
        up = _counter("paddle_tpu_overload_transitions_total",
                      model_id="m", direction="up")
        assert up >= 2

    def test_full_climb_and_full_restore(self):
        r = _FakeRouter()
        ctl = _ctl(r, hot_steps=1, cold_steps=2, cooldown_steps=0)
        r.set_depth(100)
        for want in (1, 2, 3, 4):
            assert ctl.observe() == "escalate"
            assert ctl.level == want
        assert ctl.observe() == "steady"       # capped at max_level
        assert ctl.level == len(LEVELS) - 1
        r.set_depth(0)                         # signal goes cold
        for want in (3, 2, 1, 0):
            assert ctl.observe() == "steady"   # 1st cold of each pair
            assert ctl.observe() == "de-escalate"
            assert ctl.level == want
        assert ctl.observe() == "steady"       # floor: never below 0
        assert [d for d, _ in ctl.events] == ["escalate"] * 4 + \
            ["de-escalate"] * 4
        assert _counter("paddle_tpu_overload_brownout_level",
                        model_id="m") == 0

    def test_signal_is_worst_healthy_engine(self):
        from paddle_tpu.serving.router import DRAINING
        r = _FakeRouter(n=3)
        ctl = _ctl(r)
        r.set_depth(2)                  # 0.1 s everywhere
        r.set_depth(40, i=2)            # 2.0 s on one engine
        assert ctl.signal() == pytest.approx(2.0)   # MAX, not mean
        r._hs[2].state = DRAINING       # sick engine leaves the signal
        assert ctl.signal() == pytest.approx(0.1)

    def test_level_to_action_mapping(self):
        r = _FakeRouter()
        ctl = _ctl(r)
        for lv, drafts, cap, admit_cap, cut in (
                (0, False, None, None, None),
                (1, True, None, None, None),
                (2, True, 4, None, None),
                (3, True, 4, 1, 2),      # hold batch; preempt batch
                (4, True, 4, 0, 1)):     # interactive only; preempt 1+
            ctl.level = lv               # injected ladder state
            assert ctl.drafts_paused is drafts
            assert ctl.chunk_cap() == cap
            assert ctl.admit_priority_cap() == admit_cap
            assert ctl.preempt_priority_cut() == cut

    def test_attach_detach_round_trip(self):
        r = _FakeRouter(n=2)
        ctl = _ctl(r)
        assert all(h.engine._overload is ctl for h in r.handles())
        ctl.detach()
        assert all(h.engine._overload is None for h in r.handles())


# ──────────────────────────── admission gate ────────────────────────────


class TestAdmissionGate:
    def test_doomed_deadline_sheds_with_honest_hint(self):
        r = _FakeRouter()
        ctl = _ctl(r)
        eng = r.handles()[0].engine
        eng.scheduler.queue_depth = 20      # predicted 1.0 s
        before = _counter("paddle_tpu_overload_shed_total",
                          model_id="m", cause="deadline")
        req = Request(prompt=np.arange(1, 4), deadline_s=0.5)
        with pytest.raises(AdmissionShedError) as ei:
            ctl.admission_check(eng, req)
        e = ei.value
        assert isinstance(e, BackpressureError)   # existing catch sites
        assert e.cause == "deadline"
        assert e.retry_after_s == pytest.approx(1.0)
        assert e.queue_depth == 20
        assert _counter("paddle_tpu_overload_shed_total",
                        model_id="m", cause="deadline") == before + 1
        assert ("req.shed", req.req_id, e.retry_after_s,
                "deadline") in eng._trace.events

    def test_feasible_deadline_admits(self):
        r = _FakeRouter()
        ctl = _ctl(r)
        eng = r.handles()[0].engine
        eng.scheduler.queue_depth = 4       # predicted 0.2 s
        ctl.admission_check(eng, Request(prompt=np.arange(1, 4),
                                         deadline_s=5.0))   # no raise
        ctl.admission_check(eng, Request(prompt=np.arange(1, 4)))

    def test_interactive_only_sheds_lower_tiers(self):
        r = _FakeRouter()
        ctl = _ctl(r)
        ctl.level = 4
        eng = r.handles()[0].engine
        with pytest.raises(AdmissionShedError) as ei:
            ctl.admission_check(eng, Request(prompt=np.arange(1, 4),
                                             priority=1))
        assert ei.value.cause == "brownout"
        # the premium tier still admits at interactive-only
        ctl.admission_check(eng, Request(prompt=np.arange(1, 4),
                                         priority=0))


class _AdmitPool:
    """Always-roomy pool double for FCFSScheduler.admit (the hold is
    queue policy, not page math)."""
    page_size = 4

    def prefix_match_len(self, ids):
        return 0

    def can_admit(self, max_total, pending, cached_pages=0,
                  pending_cached=0):
        return True

    def pages_needed(self, n):
        return 1


class TestAdmissionHold:
    def test_priority_cap_holds_head_and_everything_behind(self):
        """The brownout hold rides the priority-sorted queue: a held
        head means nothing behind it can overtake (no lower tier
        sneaks in a freed slot mid-brownout)."""
        sched = FCFSScheduler(max_batch_slots=4)
        batch = Request(prompt=np.arange(1, 4), priority=2)
        std = Request(prompt=np.arange(1, 4), priority=1)
        inter = Request(prompt=np.arange(1, 4), priority=0)
        for req in (batch, std, inter):
            sched.add(req)
        pool = _AdmitPool()
        # level-3 hold (cap=1): interactive + standard admit, batch holds
        got = sched.admit(4, pool, max_priority=1)
        assert [r.req_id for r in got] == [inter.req_id, std.req_id]
        assert sched.queue_depth == 1
        # level-4 hold (cap=0): nothing but interactive — batch stays
        assert sched.admit(4, pool, max_priority=0) == []
        # hold released (de-escalation): the held work admits normally
        got = sched.admit(4, pool, max_priority=None)
        assert [r.req_id for r in got] == [batch.req_id]
        assert sched.queue_depth == 0


# ──────────────────────────── real-engine lane ────────────────────────────


def _llama():
    paddle.seed(0)
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny
    return LlamaForCausalLM(llama_tiny(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_key_value_heads=2, max_position_embeddings=64))


def _armed_router(model, **cfg_kw):
    from paddle_tpu.serving import Router
    router = Router()
    router.add_model("m", model, replicas=1, page_size=4, num_pages=64,
                     max_batch_slots=2, max_model_len=64,
                     token_budget=32, min_step_tokens=32)
    cfg_kw.setdefault("hot_backlog_s", 1.0)
    cfg_kw.setdefault("cold_backlog_s", 0.25)
    ctl = OverloadController(router, config=OverloadConfig(**cfg_kw))
    return router, router.engine("m/0"), ctl


class TestQueuedExpiry:
    def test_never_admitted_work_expires_without_pages(self):
        from paddle_tpu.serving import ServingEngine
        engine = ServingEngine(_llama(), page_size=4, max_batch_slots=1)
        t0 = _counter("paddle_tpu_serving_request_timeouts_total")
        e0 = _counter("paddle_tpu_serving_expired_total")
        live = engine.add_request(np.arange(1, 5), max_new_tokens=3)
        dead = engine.add_request(np.arange(1, 4), max_new_tokens=3,
                                  deadline_s=0.0)
        peak_before = engine.pool.used_pages
        outs = engine.run()
        assert outs[dead].finish_reason == "expired"
        assert outs[dead].token_ids == [] and outs[dead].n_gen == 0
        assert outs[live].finish_reason == "length"
        assert (_counter("paddle_tpu_serving_expired_total") == e0 + 1)
        assert (_counter("paddle_tpu_serving_request_timeouts_total")
                == t0)                          # timeout never moved
        assert engine.pool.used_pages == 0 and peak_before == 0

    def test_journaled_queued_work_times_out_instead(self):
        """A queued request carrying a resume journal (migrated or
        brownout-preempted) was WORK THE FLEET TOUCHED: its deadline
        lapse retires "timeout" with the journal delivered, keeping
        "expired" an exact count of never-admitted work."""
        from paddle_tpu.serving import ServingEngine
        engine = ServingEngine(_llama(), page_size=4, max_batch_slots=1)
        e0 = _counter("paddle_tpu_serving_expired_total")
        req = Request(prompt=np.arange(1, 5), max_new_tokens=6,
                      deadline_s=0.0, resume_tokens=[7, 9])
        engine.scheduler.add(req)
        outs = engine.run()
        assert outs[req.req_id].finish_reason == "timeout"
        assert outs[req.req_id].token_ids == [7, 9]   # journal delivered
        assert _counter("paddle_tpu_serving_expired_total") == e0


class TestBrownoutPreemption:
    def test_preempted_stream_bit_identical_and_chunks_exactly_once(self):
        """The determinism contract through the ladder's sharpest move:
        a batch-tier stream journaled out of its decode slot (level 3),
        held through the brownout, and restored after de-escalation
        must end bit-identical to the same request on an undisturbed
        engine — sampling is keyed fold_in(seed, position), never slot
        — with stream seqs exactly-once across the preemption and the
        compile surface untouched."""
        PROMPT_B = np.arange(1, 9)
        PROMPT_A = np.arange(3, 7)

        # reference: same weights, no controller, no preemption
        from paddle_tpu.serving import ServingEngine
        ref = ServingEngine(_llama(), page_size=4, max_batch_slots=2,
                            num_pages=64, token_budget=32,
                            min_step_tokens=32)
        rb = ref.add_request(PROMPT_B, max_new_tokens=10,
                             temperature=0.7, seed=11, priority=2)
        ra = ref.add_request(PROMPT_A, max_new_tokens=6,
                             temperature=0.7, seed=5, priority=0)
        ref_outs = ref.run()

        router, engine, ctl = _armed_router(_llama())
        chunks = []
        b = engine.add_request(
            PROMPT_B, max_new_tokens=10, temperature=0.7, seed=11,
            priority=2,
            stream_cb=lambda rid, tok, fin, seq: chunks.append(
                (seq, tok, fin)))
        for _ in range(3):
            engine.step()                 # B decoding mid-stream
        preempt0 = _counter("paddle_tpu_serving_requests_total",
                            event="preempted", engine_id="m/0",
                            model_id="m")
        ctl.level = 3                     # injected ladder state
        engine.step()                     # lands the step in flight first
        engine.step()                     # the next plan preempts
        assert _counter("paddle_tpu_serving_requests_total",
                        event="preempted", engine_id="m/0",
                        model_id="m") == preempt0 + 1
        assert all(s is None for s in engine.slots)   # slot freed
        assert engine.pool.used_pages == 0            # pages freed
        assert engine.scheduler.queue_depth == 1      # requeued, held
        a = engine.add_request(PROMPT_A, max_new_tokens=6,
                               temperature=0.7, seed=5, priority=0)
        while engine.slots[0] is None and engine.slots[1] is None:
            engine.step()                 # interactive admits past B
        engine.step()
        assert engine.scheduler.queue_depth == 1      # B still held
        ctl.level = 0                     # storm over: release the hold
        outs = engine.run()

        assert outs[b].finish_reason == ref_outs[rb].finish_reason
        assert outs[b].token_ids == ref_outs[rb].token_ids
        assert outs[a].token_ids == ref_outs[ra].token_ids
        # stream chunks exactly-once across the preemption: seqs are a
        # gapless 0..n-1 with no duplicates, then one terminal
        toks = [c for c in chunks if c[1] is not None]
        assert [s for s, _, _ in toks] == list(
            range(len(outs[b].token_ids)))
        assert [t for _, t, _ in toks] == outs[b].token_ids
        assert chunks[-1] == (len(toks), None, outs[b].finish_reason)
        counts = engine.compile_counts()
        assert counts["step"] == counts["step_buckets"]
        assert engine.pool.used_pages == 0

    def test_preemption_skips_interactive_and_prefilling_slots(self):
        router, engine, ctl = _armed_router(_llama())
        inter = engine.add_request(np.arange(1, 5), max_new_tokens=8,
                                   priority=0)
        batch = engine.add_request(np.arange(5, 9), max_new_tokens=8,
                                   priority=2)
        for _ in range(2):
            engine.step()
        ctl.level = 3
        engine.step()      # lands the step in flight first
        engine.step()      # the next plan preempts
        live = [s.req.req_id for s in engine.slots if s is not None]
        assert inter in live and batch not in live
        ctl.level = 0
        outs = engine.run()
        assert outs[inter].n_gen == 8 and outs[batch].n_gen == 8


class TestRealEngineGate:
    def test_backpressure_hint_equals_gate_prediction(self):
        """One estimator, two consumers, on the LIVE engine: the
        bounded-queue BackpressureError hint and the overload gate's
        shed prediction are the same number for the same engine
        state."""
        router, engine, ctl = _armed_router(_llama())
        engine.add_request(np.arange(1, 5), max_new_tokens=4)
        predicted = ctl.estimator.for_engine(engine)
        assert engine._estimate_retry_after() == predicted
        with pytest.raises(AdmissionShedError) as ei:
            engine.add_request(np.arange(1, 4), max_new_tokens=4,
                               deadline_s=predicted / 100.0)
        assert ei.value.retry_after_s == predicted

    def test_shed_never_enters_queue_and_counts_rejected(self):
        router, engine, ctl = _armed_router(_llama())
        engine.add_request(np.arange(1, 5), max_new_tokens=4)
        depth = engine.scheduler.queue_depth
        r0 = _counter("paddle_tpu_serving_requests_total",
                      event="rejected", engine_id="m/0", model_id="m")
        with pytest.raises(AdmissionShedError):
            engine.add_request(np.arange(1, 4), max_new_tokens=4,
                               deadline_s=1e-9)
        assert engine.scheduler.queue_depth == depth
        assert _counter("paddle_tpu_serving_requests_total",
                        event="rejected", engine_id="m/0",
                        model_id="m") == r0 + 1
        # the engine still serves admitted work afterwards
        outs = engine.run()
        assert all(o.finish_reason == "length" for o in outs.values())
