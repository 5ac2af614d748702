"""paddle_tpu.faults: injection framework + serving resilience layer.

Acceptance gates (ISSUE 4): the chaos suite proves the no-poison
invariant (a NaN fault in one sequence's logits leaves batch-mates
token-identical to a fault-free run, the victim retires with a distinct
finish_reason, and its pages return to the pool), deadline/cancel paths
increment their counters exactly once per event, ``/healthz`` flips to
non-OK while the watchdog is tripped and recovers afterward, and the
decode program still compiles exactly once under injection.

Everything here is deterministic: seeded schedules, injectable clocks
and sleeps, greedy (temperature-0) sampling — and hermetic: every
``faults.inject`` is context-manager scoped, and all metric assertions
are deltas against the process-global registry.
"""
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import faults, metrics
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import (BackpressureError, CompletionAPI,
                                PagedKVCachePool, Router, ServingEngine)

pytestmark = pytest.mark.faults


def _llama():
    paddle.seed(0)
    return LlamaForCausalLM(llama_tiny(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_key_value_heads=2, max_position_embeddings=64))


def _tiny_llama():
    paddle.seed(0)
    return LlamaForCausalLM(llama_tiny(
        vocab_size=32, hidden_size=16, num_layers=1, num_heads=1,
        num_key_value_heads=1, max_position_embeddings=32))


_PROMPTS = [np.random.RandomState(7).randint(0, 128, (n,))
            for n in (5, 9, 3, 4)]


def _counter(name, **labels):
    fam = metrics.get_registry().get(name)
    if fam is None:
        return 0.0
    if labels and set(labels) != set(fam.label_names):
        # partial label set: aggregate the unnamed dimensions (e.g.
        # jit_compiles_total{fn=...} summed across its source split)
        return fam.sum_labels(**labels)
    return (fam.labels(**labels) if labels else fam).value


@pytest.fixture(autouse=True)
def _no_leaked_faults():
    """Belt-and-braces hermeticity: no armed fault survives a test."""
    faults.reset()
    yield
    assert faults.active_faults() == []
    faults.reset()


# ─────────────────────────── injection framework ───────────────────────────


class TestFaultPoints:
    def test_unarmed_point_is_free_and_inert(self):
        faults.point("nonexistent.point")  # no spec -> no-op, no error

    def test_scoping_is_hermetic(self):
        with faults.inject("t.scope", raise_=faults.FaultInjected):
            with pytest.raises(faults.FaultInjected):
                faults.point("t.scope")
        faults.point("t.scope")  # disarmed on exit

    def test_raise_once_schedule(self):
        with faults.inject("t.once", raise_=RuntimeError, times=1) as spec:
            with pytest.raises(RuntimeError):
                faults.point("t.once")
            for _ in range(5):
                faults.point("t.once")  # fired out
        assert spec.fired == 1 and spec.hits == 6

    def test_every_n_and_after_schedule(self):
        fired = []
        with faults.inject("t.sched", call=lambda: fired.append(1),
                           every=3, after=2) as spec:
            for _ in range(11):
                faults.point("t.sched")
        # hits 1,2 skipped; then fires on hits 3, 6, 9 (every 3rd)
        assert spec.hits == 11 and len(fired) == 3

    def test_probability_gate_is_seeded_deterministic(self):
        def count(seed):
            with faults.inject("t.p", call=lambda: None, p=0.5,
                               seed=seed) as spec:
                for _ in range(50):
                    faults.point("t.p")
            return spec.fired

        a, b = count(3), count(3)
        assert a == b and 0 < a < 50
        assert count(4) != a or count(5) != a  # different seed, new draw

    def test_raise_instance_and_class_and_exhaustion_type(self):
        err = ValueError("specific")
        with faults.inject("t.inst", raise_=err, times=1):
            with pytest.raises(ValueError, match="specific"):
                faults.point("t.inst")
        with faults.inject("t.cls", raise_=faults.ResourceExhausted,
                           times=1):
            with pytest.raises(faults.ResourceExhausted, match="t.cls"):
                faults.point("t.cls")

    def test_firing_increments_point_labeled_metric(self):
        before = _counter("paddle_tpu_faults_injected_total",
                          point="t.metric")
        with faults.inject("t.metric", delay_s=0.0001, times=2):
            faults.point("t.metric")
            faults.point("t.metric")
            faults.point("t.metric")  # schedule exhausted: not counted
        assert _counter("paddle_tpu_faults_injected_total",
                        point="t.metric") == before + 2

    def test_spec_validation(self):
        with pytest.raises(ValueError, match="do something"):
            faults.FaultSpec("t.x")
        with pytest.raises(ValueError):
            faults.FaultSpec("t.x", delay_s=1, every=0)
        with pytest.raises(ValueError):
            faults.FaultSpec("t.x", delay_s=1, p=1.5)

    def test_known_points_catalog_covers_serving(self):
        pts = faults.known_points()
        for name in ("serving.step", "serving.prefill",
                     "serving.decode_step", "serving.compile_step",
                     "serving.kv_alloc"):
            assert name in pts and pts[name]


# ──────────────────────── retry / deadline / watchdog ────────────────────────


class TestRetryAndDeadline:
    def test_backoff_delays_deterministic_capped(self):
        a = list(faults.backoff_delays(6, base_delay_s=0.1, factor=2.0,
                                       max_delay_s=0.5, jitter=0.5, seed=9))
        b = list(faults.backoff_delays(6, base_delay_s=0.1, factor=2.0,
                                       max_delay_s=0.5, jitter=0.5, seed=9))
        assert a == b and len(a) == 5
        assert all(d <= 0.5 for d in a)
        nojit = list(faults.backoff_delays(4, base_delay_s=0.1,
                                           jitter=0.0, max_delay_s=10.0))
        assert nojit == [0.1, 0.2, 0.4]

    def test_retry_recovers_and_reraises_original(self):
        slept, calls = [], []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        assert faults.retry(flaky, attempts=3, base_delay_s=0.01,
                            sleep=slept.append) == "ok"
        assert len(calls) == 3 and len(slept) == 2

        with pytest.raises(OSError, match="always"):
            faults.retry(lambda: (_ for _ in ()).throw(OSError("always")),
                         attempts=2, base_delay_s=0.0, sleep=lambda d: None)

    def test_retry_honors_deadline(self):
        t = [0.0]
        dl = faults.Deadline(1.0, clock=lambda: t[0])

        def fail():
            t[0] += 0.7  # two failures burn past the 1s budget
            raise OSError("transient")

        with pytest.raises(faults.DeadlineExceeded) as ei:
            faults.retry(fail, attempts=10, base_delay_s=0.0,
                         sleep=lambda d: None, deadline=dl)
        assert isinstance(ei.value.__cause__, OSError)

    def test_deadline_basics(self):
        assert not faults.Deadline.never().expired()
        assert faults.Deadline.never().remaining() == float("inf")
        assert faults.Deadline(-1).expired()
        t = [0.0]
        dl = faults.Deadline(2.0, clock=lambda: t[0])
        assert not dl.expired() and dl.remaining() == 2.0
        t[0] = 2.5
        assert dl.expired()
        with pytest.raises(faults.DeadlineExceeded, match="decode"):
            dl.check("decode")


class TestStepWatchdog:
    def test_trip_recover_state_machine(self):
        wd = faults.StepWatchdog(stall_threshold_s=1.0, recovery_steps=2)
        assert wd.end_step(0.5) is False and wd.status() == "ok"
        assert wd.end_step(1.5) is True          # healthy -> tripped
        assert wd.end_step(2.0) is False         # still tripped: ONE episode
        assert wd.trips == 1 and wd.status() == "degraded"
        wd.end_step(0.1)
        assert wd.status() == "degraded"         # 1 healthy < recovery_steps
        wd.end_step(0.1)
        assert wd.status() == "ok"               # recovered
        assert wd.end_step(9.9) is True and wd.trips == 2  # new episode

    def test_stalled_now_detects_live_hang_from_other_thread(self):
        t = [0.0]
        wd = faults.StepWatchdog(stall_threshold_s=1.0, clock=lambda: t[0])
        wd.begin_step()
        t[0] = 0.5
        assert not wd.stalled_now() and wd.status() == "ok"
        t[0] = 1.6                               # step still hasn't returned
        assert wd.stalled_now() and wd.status() == "degraded"
        assert wd.end_step() is True             # measured from begin_step


# ─────────────────────────── serving chaos suite ───────────────────────────


class TestServingChaos:
    def test_nan_quarantine_no_poison_invariant(self):
        """THE acceptance test: NaN injected into one sequence's KV (so
        its logits go non-finite) — batch-mates token-identical to a
        fault-free run, victim retires "nan", pages recover, decode
        compiled exactly once."""
        model = _llama()
        # fault-free reference run
        eng0 = ServingEngine(model, page_size=4, max_batch_slots=2)
        m0 = eng0.add_request(_PROMPTS[0], max_new_tokens=8)
        v0 = eng0.add_request(_PROMPTS[1], max_new_tokens=8)
        ref = eng0.run()
        assert ref[m0].finish_reason == "length"

        jit_before = _counter("paddle_tpu_jit_compiles_total",
                              fn="serving_step")
        nan_before = _counter("paddle_tpu_serving_nan_quarantines_total")
        eng = ServingEngine(model, page_size=4, max_batch_slots=2)
        mate = eng.add_request(_PROMPTS[0], max_new_tokens=8)
        victim = eng.add_request(_PROMPTS[1], max_new_tokens=8)
        eng.step()  # both prefilled + one clean decode step
        baseline_free = eng.pool.used_pages
        assert baseline_free > 0
        with faults.inject("serving.decode_step",
                           call=lambda: eng.pool.poison_seq(victim),
                           times=1) as spec:
            outs = eng.run()
        assert spec.fired == 1
        # victim: quarantined with a distinct reason, tokens BEFORE the
        # poisoned step only, never the garbage sample
        assert outs[victim].finish_reason == "nan"
        assert 1 <= outs[victim].n_gen < 8
        # batch-mate: token-identical to the fault-free run
        np.testing.assert_array_equal(np.asarray(outs[mate].token_ids),
                                      np.asarray(ref[m0].token_ids))
        assert outs[mate].finish_reason == "length"
        # pages recovered to baseline (everything drained -> 0 used)
        assert eng.pool.used_pages == 0
        # telemetry: one quarantine, and the unified step compiled
        # EXACTLY once per token-grid bucket despite the injection
        assert (_counter("paddle_tpu_serving_nan_quarantines_total")
                == nan_before + 1)
        counts = eng.compile_counts()
        assert counts["step"] == counts["step_buckets"]
        assert (_counter("paddle_tpu_jit_compiles_total",
                         fn="serving_step") == jit_before + counts["step"])

    def test_prefill_nan_quarantined_before_any_token(self):
        """A non-finite PREFILL must quarantine before any page is
        allocated or any token streamed — the first sample is as
        untrustworthy as a decode-step one."""
        import jax.numpy as jnp

        model = _tiny_llama()
        for p in model.parameters():  # poison the whole model: every
            p._value = jnp.full_like(p._value, jnp.nan)  # logit goes NaN
        engine = ServingEngine(model, page_size=4, max_batch_slots=1)
        streamed = []
        rid = engine.add_request(np.arange(1, 5), max_new_tokens=4,
                                 stream_cb=lambda r, t, d:
                                 streamed.append((t, d)))
        outs = engine.run()
        assert outs[rid].finish_reason == "nan" and outs[rid].n_gen == 0
        assert engine.pool.used_pages == 0
        # only the terminal callback fired; no garbage token streamed
        assert streamed == [(None, "nan")]

    def test_page_pool_exhaustion_mid_decode_drains(self):
        """ONE injected allocation failure mid-decode: the victim
        quarantines with "error", batch-mates decode on, and queued work
        still drains — no deadlock, no page leak."""
        model = _llama()
        engine = ServingEngine(model, page_size=4, max_batch_slots=2)
        # victim prompt is 4 tokens: its chunked prefill exactly fills
        # page_size=4, so ITS first decode append needs a fresh page —
        # which is where the armed fault lands (the len-3 mate's first
        # decode still fits its prefill page)
        victim = engine.add_request(_PROMPTS[3], max_new_tokens=6)
        mate = engine.add_request(_PROMPTS[2], max_new_tokens=6)
        queued = engine.add_request(_PROMPTS[2], max_new_tokens=4)
        # the first call admits and chunks victim and mate (queued waits:
        # 2 slots), each chunk drawing a page, then plans their first
        # decode step behind it: the victim's fresh page is the third draw
        with faults.inject("serving.kv_alloc",
                           raise_=faults.ResourceExhausted, times=1,
                           after=2):
            engine.step()
            outs = engine.run()
        assert len(outs) == 3
        assert outs[victim].finish_reason == "error"
        assert "ResourceExhausted" in outs[victim].error
        assert outs[mate].finish_reason == "length"
        assert outs[mate].n_gen == 6
        assert outs[queued].finish_reason == "length"  # drained after free
        assert engine.pool.used_pages == 0
        counts = engine.compile_counts()
        assert counts["step"] == counts["step_buckets"]

    def test_exhaustion_during_prefill_allocate_rolls_back(self):
        """An allocation failure inside prefill fails only that request
        (atomic rollback: no half-built sequence, no leaked pages)."""
        model = _tiny_llama()
        engine = ServingEngine(model, page_size=4, max_batch_slots=1)
        rid = engine.add_request(np.arange(1, 7), max_new_tokens=2)  # 2 pages
        ok = engine.add_request(np.arange(1, 4), max_new_tokens=2)
        with faults.inject("serving.kv_alloc",
                           raise_=faults.ResourceExhausted, times=1,
                           after=1):  # second page of the first allocate
            outs = engine.run()
        assert outs[rid].finish_reason == "error" and outs[rid].n_gen == 0
        assert outs[ok].finish_reason == "length"
        assert engine.pool.used_pages == 0 and not engine.pool.has_seq(rid)

    def test_pool_allocate_rollback_unit(self):
        pool = PagedKVCachePool(num_layers=1, num_pages=9, page_size=4,
                                n_kv_heads=2, head_dim=8)
        with faults.inject("serving.kv_alloc",
                           raise_=faults.ResourceExhausted, after=1):
            with pytest.raises(faults.ResourceExhausted):
                pool.allocate("a", 10)  # needs 3 pages; dies on the 2nd
        assert pool.used_pages == 0 and not pool.has_seq("a")
        assert pool.allocate("b", 10)  # pool fully usable afterwards

    def test_compile_failure_retried_compiles_once(self):
        model = _tiny_llama()
        engine = ServingEngine(model, page_size=4, max_batch_slots=1)
        retries_before = _counter("paddle_tpu_faults_retries_total")
        rid = engine.add_request(np.arange(1, 5), max_new_tokens=3)
        with faults.inject("serving.compile_step",
                           raise_=RuntimeError("flaky XLA"), times=1) as sp:
            outs = engine.run()
        assert sp.fired == 1
        assert outs[rid].finish_reason == "length" and outs[rid].n_gen == 3
        assert _counter("paddle_tpu_faults_retries_total") > retries_before
        counts = engine.compile_counts()
        assert counts["step"] == counts["step_buckets"]

    def test_deadline_expiry_queued_and_mid_decode(self):
        model = _tiny_llama()
        engine = ServingEngine(model, page_size=4, max_batch_slots=1)
        before = _counter("paddle_tpu_serving_request_timeouts_total")
        before_exp = _counter("paddle_tpu_serving_expired_total")
        live = engine.add_request(np.arange(1, 5), max_new_tokens=4)
        dead = engine.add_request(np.arange(1, 4), max_new_tokens=4,
                                  deadline_s=0.0)  # expired while queued
        engine.step()
        # queued lapse retires "expired" (ISSUE 19): the fleet never
        # touched this work — pages never allocated, no tokens owed
        assert (_counter("paddle_tpu_serving_expired_total")
                == before_exp + 1)
        assert (_counter("paddle_tpu_serving_request_timeouts_total")
                == before)
        # now expire the RUNNING request mid-decode (injected clock state:
        # an already-elapsed deadline) — admitted work stays "timeout"
        engine.slots[0].req.deadline = faults.Deadline(-1.0)
        outs = engine.run()
        assert outs[dead].finish_reason == "expired" and outs[dead].n_gen == 0
        assert outs[live].finish_reason == "timeout"
        assert 1 <= outs[live].n_gen < 4  # partial tokens delivered
        assert (_counter("paddle_tpu_serving_request_timeouts_total")
                == before + 1)  # exactly once per event
        assert (_counter("paddle_tpu_serving_expired_total")
                == before_exp + 1)
        assert engine.pool.used_pages == 0

    def test_cancel_while_queued_and_while_decoding(self):
        model = _tiny_llama()
        engine = ServingEngine(model, page_size=4, max_batch_slots=1)
        before = _counter("paddle_tpu_serving_cancellations_total")
        running = engine.add_request(np.arange(1, 5), max_new_tokens=6)
        waiting = engine.add_request(np.arange(1, 4), max_new_tokens=6)
        engine.step()
        assert engine.cancel(waiting) is True        # cancel-while-queued
        assert engine.scheduler.queue_depth == 0
        assert engine.cancel(running) is True        # cancel-while-decoding
        assert engine.pool.used_pages == 0           # pages freed THIS call
        assert engine.slots[0] is None
        assert engine.cancel(running) is False       # idempotent
        assert engine.cancel("no-such-id") is False
        outs = engine.run()
        assert outs[waiting].finish_reason == "cancelled"
        assert outs[waiting].n_gen == 0
        assert outs[running].finish_reason == "cancelled"
        assert outs[running].n_gen >= 1
        assert (_counter("paddle_tpu_serving_cancellations_total")
                == before + 2)  # exactly once per event

    def test_cancel_reentrant_from_stream_callback(self):
        """cancel() issued from a request's OWN stream callback (the
        client-disconnect idiom) must retire it cleanly wherever it is
        — mid-prefill or mid-decode, even on what would have been its
        terminal token — without double-freeing pages."""
        model = _tiny_llama()
        # mid-prefill: cancel on the FIRST streamed token
        eng1 = ServingEngine(model, page_size=4, max_batch_slots=1)
        r1 = eng1.add_request(
            np.arange(1, 5), max_new_tokens=4,
            stream_cb=lambda rid, tok, done: eng1.cancel(rid)
            if not done else None)
        outs = eng1.run()
        assert outs[r1].finish_reason == "cancelled" and outs[r1].n_gen <= 1
        assert eng1.pool.used_pages == 0 and eng1.slots[0] is None
        # mid-decode, on the token that would have finished the request
        # (max_new_tokens reached): cancel must win without a KeyError
        eng2 = ServingEngine(model, page_size=4, max_batch_slots=1)
        seen = []

        def cb(rid, tok, done):
            if not done:
                seen.append(tok)
                if len(seen) == 2:  # 2nd token == max_new_tokens'th
                    eng2.cancel(rid)

        r2 = eng2.add_request(np.arange(1, 5), max_new_tokens=2,
                              stream_cb=cb)
        outs = eng2.run()
        assert outs[r2].finish_reason == "cancelled"
        assert eng2.pool.used_pages == 0 and eng2.slots[0] is None

    def test_bounded_queue_backpressure_retry_after(self):
        model = _tiny_llama()
        engine = ServingEngine(model, page_size=4, max_batch_slots=1,
                               max_queue=1)
        rej_before = _counter("paddle_tpu_serving_queue_rejections_total")
        ok = engine.add_request(np.arange(1, 4), max_new_tokens=2)
        with pytest.raises(BackpressureError, match="max_queue=1") as ei:
            engine.add_request(np.arange(1, 4), max_new_tokens=2)
        assert ei.value.retry_after_s > 0 and ei.value.queue_depth == 1
        assert (_counter("paddle_tpu_serving_queue_rejections_total")
                == rej_before + 1)
        outs = engine.run()  # the admitted request is unharmed
        assert outs[ok].finish_reason == "length"
        engine.add_request(np.arange(1, 4), max_new_tokens=1)  # room again

    def test_stream_callback_exception_isolated(self):
        model = _llama()
        engine = ServingEngine(model, page_size=4, max_batch_slots=2)
        cb_before = _counter("paddle_tpu_serving_callback_errors_total")
        seen = []

        def bad_cb(rid, tok, done):
            seen.append(tok)
            if len(seen) >= 2:
                raise ValueError("user callback bug")

        bad = engine.add_request(_PROMPTS[0], max_new_tokens=6,
                                 stream_cb=bad_cb)
        good = engine.add_request(_PROMPTS[1], max_new_tokens=6)
        outs = engine.run()  # must NOT raise
        assert outs[bad].finish_reason == "error"
        assert outs[bad].n_gen == 2  # retired at the offending token
        assert outs[good].finish_reason == "length" and outs[good].n_gen == 6
        assert (_counter("paddle_tpu_serving_callback_errors_total")
                == cb_before + 1)
        assert engine.pool.used_pages == 0

    def test_api_chunk_cb_isolation_and_reason_passthrough(self):
        engine = ServingEngine(_llama(), page_size=4, max_batch_slots=2)
        api = CompletionAPI(engine)

        def exploding(chunk):
            raise RuntimeError("user stream handler bug")

        resp = api.create_completion(_PROMPTS[2], max_tokens=4,
                                     stream_cb=exploding)
        assert resp["choices"][0]["finish_reason"] == "error"

    def test_watchdog_trips_healthz_degrades_and_recovers(self):
        model = _tiny_llama()
        engine = ServingEngine(model, page_size=4, max_batch_slots=1,
                               watchdog_stall_s=0.005,
                               watchdog_recovery_steps=2)
        trips_before = _counter("paddle_tpu_serving_watchdog_trips_total")
        with metrics.MetricsServer(health_cb=engine.health, port=0) as srv:
            url = f"{srv.url}/healthz"
            with urllib.request.urlopen(url) as r:
                assert r.status == 200
                assert json.loads(r.read())["status"] == "ok"
            with faults.inject("serving.step", delay_s=0.02, times=1):
                engine.step()  # over-threshold step -> trip
            assert (_counter("paddle_tpu_serving_watchdog_trips_total")
                    == trips_before + 1)
            assert _counter("paddle_tpu_serving_degraded",
                            engine_id=engine.engine_id,
                            model_id=engine.model_id) == 1.0
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(url)
            assert ei.value.code == 503
            assert json.loads(ei.value.read())["status"] == "degraded"
            engine.step()  # two healthy (empty) steps -> recovery
            engine.step()
            assert _counter("paddle_tpu_serving_degraded",
                            engine_id=engine.engine_id,
                            model_id=engine.model_id) == 0.0
            with urllib.request.urlopen(url) as r:
                assert r.status == 200
        # one trip episode, counted exactly once
        assert (_counter("paddle_tpu_serving_watchdog_trips_total")
                == trips_before + 1)


# ───────────────── refcount-aware scrub (ISSUE 8 satellite) ─────────────────


class TestRefcountAwareScrub:
    """A quarantined victim must never scrub pages a healthy sibling
    (fork or prefix cache) still reads — the scrub defers until the LAST
    reference drops, then converts to a real lazy zero before reuse; and
    ``poison_seq`` refuses to poison shared pages outright (attention
    reads shared bytes for real — poisoning them is a different drill)."""

    def _pool(self):
        import jax.numpy as jnp

        from paddle_tpu.serving import PagedKVCachePool as P

        pool = P(num_layers=1, num_pages=9, page_size=4, n_kv_heads=2,
                 head_dim=8)
        k = jnp.full((9, 4, 2, 8), 7.0, jnp.float32)
        pool.set_arrays([k], [k + 1.0])
        return pool

    def test_scrub_defers_while_sibling_holds_reference(self):
        pool = self._pool()
        pool.allocate("src", 6)
        table = pool.block_table("src")
        pool.fork("src", "dst")  # every page shared (ref 2)
        pool.free("src", scrub=True)  # quarantine while dst still reads
        # nothing freed, nothing zeroed: the sibling's bytes are intact
        assert pool.used_pages == 2
        np.testing.assert_array_equal(
            np.asarray(pool.k_pools[0]._value[np.asarray(table)]), 7.0)
        # last reference drops via a NORMAL free — the deferred mark
        # must still convert: the pages are zeroed before reuse
        pool.free("dst")
        assert pool.used_pages == 0
        t2 = pool.allocate("new", 8)
        assert set(table) <= set(t2)  # LIFO free list: same pages reused
        np.testing.assert_array_equal(
            np.asarray(pool.k_pools[0]._value[np.asarray(table)]), 0.0)

    def test_poison_seq_refuses_shared_pages_poisons_exclusive(self):
        pool = self._pool()
        pool.allocate("src", 6)
        src_table = pool.block_table("src")
        pool.fork("src", "dst")
        with pytest.raises(ValueError, match="shared"):
            pool.poison_seq("src")  # every page shared: would corrupt dst
        pool.extend("dst", 7)  # divergent append -> CoW private tail
        n = pool.poison_seq("dst")
        assert n == 3  # only the private tail's written slots (4..6)
        # src's pages (including the once-shared tail) stay finite
        src_k = np.asarray(pool.k_pools[0]._value[np.asarray(src_table)])
        assert np.isfinite(src_k).all()

    def test_nan_quarantine_evicts_suspect_prefix_nodes(self):
        """Prefix nodes inserted FROM a poisoned request's prefill must
        stop serving matches (quarantine x refcount seam): the victim's
        prompt re-runs as a MISS afterward, while a healthy tenant's
        cached prefix keeps hitting."""
        model = _llama()
        eng = ServingEngine(model, page_size=4, max_batch_slots=2)
        lbl = dict(engine_id=eng.engine_id, model_id=eng.model_id)
        # DISTINCT prompts (the module _PROMPTS all share a seed-7 prefix
        # and would legitimately keep matching each other's first page)
        healthy_p = np.random.RandomState(50).randint(0, 128, (5,))
        victim_p = np.random.RandomState(99).randint(0, 128, (9,))
        eng.add_request(healthy_p, max_new_tokens=2)  # healthy prefix
        eng.run()
        victim = eng.add_request(victim_p, max_new_tokens=8)
        eng.step()
        with faults.inject("serving.decode_step",
                           call=lambda: eng.pool.poison_seq(victim),
                           times=1):
            outs = eng.run()
        assert outs[victim].finish_reason == "nan"
        h0 = _counter("paddle_tpu_serving_prefix_hits_total", **lbl)
        m0 = _counter("paddle_tpu_serving_prefix_misses_total", **lbl)
        eng.add_request(victim_p, max_new_tokens=2)  # victim's prompt
        eng.run()
        assert _counter("paddle_tpu_serving_prefix_misses_total",
                        **lbl) == m0 + 1  # suspect prefix evicted
        eng.add_request(healthy_p, max_new_tokens=2)  # healthy prompt
        eng.run()
        assert _counter("paddle_tpu_serving_prefix_hits_total",
                        **lbl) == h0 + 1  # healthy prefix still serves
        assert eng.pool.used_pages == 0


# ──────────────────────── front-door satellites ────────────────────────


class TestFrontDoorSatellites:
    def test_check_request_messages_name_limit_and_value(self):
        engine = ServingEngine(_tiny_llama(), page_size=4, num_pages=4,
                               max_batch_slots=1)  # max_model_len=32
        with pytest.raises(ValueError, match=r"max_model_len=32"):
            engine.check_request(40, 1)  # prompt alone over the cap
        with pytest.raises(ValueError, match=r"at most 2"):
            engine.check_request(30, 10)  # total over the cap
        with pytest.raises(ValueError,
                           match=r"usable pages.*num_pages=4.*page_size=4"):
            engine.check_request(10, 10)  # 5 pages > 3 usable

    def test_step_crash_closes_watchdog_bracket(self):
        """An exception escaping step() must still close the watchdog
        bracket (finally): an idle engine must not read as live-hung on
        /healthz forever after one crashed step."""
        import time as _time

        engine = ServingEngine(_tiny_llama(), page_size=4,
                               max_batch_slots=1, watchdog_stall_s=0.003)
        with faults.inject("serving.step", raise_=faults.FaultInjected,
                           times=1):
            with pytest.raises(faults.FaultInjected):
                engine.step()
        _time.sleep(0.01)  # idle well past the stall threshold
        assert not engine.watchdog.stalled_now()
        assert engine.health()["status"] == "ok"

    def test_invalid_prompt_mid_batch_leaves_no_orphans(self):
        """A Request-invariant failure (empty prompt) partway through a
        batch must un-queue the already-added mates, same as
        backpressure."""
        engine = ServingEngine(_tiny_llama(), page_size=4,
                               max_batch_slots=1)
        api = CompletionAPI(engine)
        with pytest.raises(ValueError, match="empty prompt"):
            api.create_completion([np.arange(1, 4), np.zeros(0, np.int32)],
                                  max_tokens=2)
        assert engine.scheduler.queue_depth == 0 and not engine.has_work

    def test_backpressure_mid_batch_leaves_no_orphans(self):
        """A bounded queue filling up mid-batch must cancel the mates
        already queued — they must not run as orphans under the next
        create_completion."""
        engine = ServingEngine(_tiny_llama(), page_size=4,
                               max_batch_slots=1, max_queue=1)
        api = CompletionAPI(engine)
        with pytest.raises(BackpressureError):
            api.create_completion([np.arange(1, 4), np.arange(1, 4)],
                                  max_tokens=2)
        assert engine.scheduler.queue_depth == 0 and not engine.has_work
        resp = api.create_completion(np.arange(1, 4), max_tokens=2)
        assert resp["choices"][0]["finish_reason"] == "length"

    def test_router_unknown_engine_and_idle_tie_rotation(self):
        # the old EnginePool bounds/next() contract, on the Router
        # surface: bad ids raise actionably, idle ties rotate modularly
        router = Router()
        router.add_model("default", _tiny_llama(), replicas=2,
                         page_size=4, max_batch_slots=1)
        with pytest.raises(KeyError, match="unknown engine id"):
            router.engine("default/9")
        a, b, c = (router.select().engine_id for _ in range(3))
        assert a != b and c == a


class TestLockSanitizer:
    """Runtime half of the TPL007-009 contract (docs/RESILIENCE.md
    "Lock ordering"): the sanitizer must see what the static rules can
    only infer — actual cross-thread acquisition order."""

    def test_consistent_order_is_clean(self):
        import threading
        san = faults.LockSanitizer(order=("router", "engine"))
        a = san.wrap(threading.Lock(), "router")
        b = san.wrap(threading.Lock(), "engine")

        def fwd():
            for _ in range(20):
                with a:
                    with b:
                        pass
        t = threading.Thread(target=fwd)
        t.start()
        t.join()
        with a:
            with b:
                pass
        san.assert_clean()
        assert san.report() == "LockSanitizer: clean"

    def test_two_thread_inversion_detected(self):
        import threading
        san = faults.LockSanitizer(order=("router", "engine"))
        a = san.wrap(threading.Lock(), "router")
        b = san.wrap(threading.Lock(), "engine")
        with a:
            with b:
                pass

        def rev():   # never concurrent with fwd — no real deadlock,
            with b:  # but the hazard must still be reported
                with a:
                    pass
        t = threading.Thread(target=rev)
        t.start()
        t.join()
        kinds = {v.kind for v in san.violations}
        assert "order-inversion" in kinds
        assert "canonical-order" in kinds   # rank check needs no 2nd path
        inv = next(v for v in san.violations
                   if v.kind == "order-inversion")
        assert inv.locks == ("engine", "router")
        assert "router -> engine" in inv.detail   # both witnesses named
        assert "engine -> router" in inv.detail
        with pytest.raises(AssertionError, match="order-inversion"):
            san.assert_clean()

    def test_rlock_reentry_is_legal(self):
        import threading
        san = faults.LockSanitizer()
        r = san.wrap(threading.RLock(), "r")
        with r:
            with r:
                assert r.locked()   # owned-by-me for the RLock duck type
        san.assert_clean()

    def test_nonreentrant_reacquire_raises_instead_of_deadlocking(self):
        import threading
        san = faults.LockSanitizer()
        p = san.wrap(threading.Lock(), "p")
        with p:
            with pytest.raises(RuntimeError, match="would deadlock"):
                p.acquire()
        assert [v.kind for v in san.violations] == [
            "non-reentrant-reacquire"]

    def test_leaf_lock_must_not_nest(self):
        import threading
        san = faults.LockSanitizer(leaves=("metrics.registry",))
        leaf = san.wrap(threading.Lock(), "metrics.registry")
        other = san.wrap(threading.Lock(), "other")
        with other:      # acquiring a leaf while holding others: fine
            with leaf:
                pass
        san.assert_clean()
        # a fresh sanitizer (so the reverse edge above doesn't ALSO
        # read as an inversion): holding a leaf across an acquisition
        san2 = faults.LockSanitizer(leaves=("metrics.registry",))
        leaf2 = san2.wrap(threading.Lock(), "metrics.registry")
        other2 = san2.wrap(threading.Lock(), "other")
        with leaf2:
            with other2:
                pass
        assert [v.kind for v in san2.violations] == ["leaf-holds"]

    def test_attach_restores_and_metrics_flow(self):
        import threading

        class Holder:
            def __init__(self):
                self._lock = threading.Lock()

        h = Holder()
        san = faults.LockSanitizer()
        orig = san.attach(h, "_lock", "holder")
        hold0 = metrics.get_registry().get(
            "paddle_tpu_lock_hold_seconds").labels(lock="holder").count
        wait0 = metrics.get_registry().get(
            "paddle_tpu_lock_wait_seconds").labels(lock="holder").count
        with h._lock:
            pass
        assert metrics.get_registry().get(
            "paddle_tpu_lock_hold_seconds").labels(
                lock="holder").count == hold0 + 1
        assert metrics.get_registry().get(
            "paddle_tpu_lock_wait_seconds").labels(
                lock="holder").count == wait0 + 1
        h._lock = orig          # the finally-restore idiom
        assert h._lock is orig

    def test_violations_deduplicate(self):
        import threading
        san = faults.LockSanitizer()
        a = san.wrap(threading.Lock(), "a")
        b = san.wrap(threading.Lock(), "b")

        def once(first, second):
            with first:
                with second:
                    pass
        v0 = metrics.get_registry().get(
            "paddle_tpu_lock_order_violations_total").value
        for _ in range(5):      # same inversion five times -> one record
            t = threading.Thread(target=once, args=(a, b))
            t.start()
            t.join()
            t = threading.Thread(target=once, args=(b, a))
            t.start()
            t.join()
        inv = [v for v in san.violations if v.kind == "order-inversion"]
        assert len(inv) == 1
        assert metrics.get_registry().get(
            "paddle_tpu_lock_order_violations_total").value == v0 + 1
