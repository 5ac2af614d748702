"""Profiler: scheduler states, RecordEvent spans, chrome-trace export,
summary tables (reference: python/paddle/profiler/profiler.py:340,
utils.py:37)."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.profiler import (Profiler, ProfilerState, ProfilerTarget,
                                 RecordEvent, export_chrome_tracing,
                                 load_profiler_result, make_scheduler)


class TestScheduler:
    def test_make_scheduler_states(self):
        sched = make_scheduler(closed=1, ready=1, record=2, repeat=1,
                               skip_first=1)
        states = [sched(i) for i in range(6)]
        assert states == [
            ProfilerState.CLOSED,      # skip_first
            ProfilerState.CLOSED,      # closed
            ProfilerState.READY,       # ready
            ProfilerState.RECORD,      # record
            ProfilerState.RECORD_AND_RETURN,  # last record step
            ProfilerState.CLOSED,      # repeat exhausted
        ]

    def test_default_scheduler_always_records(self):
        p = Profiler(targets=[ProfilerTarget.CPU], trace_dir="/tmp/_pt_prof0")
        assert p._scheduler(0) == ProfilerState.RECORD

    def test_bad_scheduler_args(self):
        with pytest.raises(ValueError):
            make_scheduler(closed=-1, ready=0, record=1)
        with pytest.raises(ValueError):
            make_scheduler(closed=0, ready=0, record=0)


class TestProfiler:
    def test_record_events_and_export(self, tmp_path):
        traced = []
        p = Profiler(targets=[ProfilerTarget.CPU],
                     scheduler=make_scheduler(closed=0, ready=0, record=2,
                                              repeat=1),
                     on_trace_ready=export_chrome_tracing(str(tmp_path)),
                     trace_dir=str(tmp_path))
        p.start()
        for _ in range(2):
            with RecordEvent("forward"):
                x = paddle.to_tensor(np.ones((4, 4), "float32"))
                (x @ x).numpy()
            with RecordEvent("backward"):
                pass
            p.step()
        p.stop()
        files = [f for f in os.listdir(tmp_path)
                 if f.endswith(".paddle_trace.json")]
        assert files, "no chrome trace exported"
        trace = load_profiler_result(os.path.join(tmp_path, files[0]))
        names = {e["name"] for e in trace["traceEvents"]}
        assert "forward" in names and "backward" in names
        for e in trace["traceEvents"]:
            assert e["ph"] == "X" and e["dur"] >= 0

    def test_record_event_noop_when_closed(self, tmp_path):
        p = Profiler(targets=[ProfilerTarget.CPU],
                     scheduler=lambda step: ProfilerState.CLOSED,
                     trace_dir=str(tmp_path))
        p.start()
        with RecordEvent("invisible"):
            pass
        p.stop()
        assert p._events == []

    def test_record_event_decorator_and_begin_end(self, tmp_path):
        p = Profiler(targets=[ProfilerTarget.CPU], trace_dir=str(tmp_path),
                     on_trace_ready=lambda prof: None)
        p.start()

        @RecordEvent("decorated")
        def f():
            return 1

        f()
        ev = RecordEvent("manual")
        ev.begin()
        ev.end()
        p.stop()
        names = [n for n, _, _ in p._hist_events + p._events]
        assert "decorated" in names and "manual" in names

    def test_summary_table(self, tmp_path, capsys):
        p = Profiler(targets=[ProfilerTarget.CPU], trace_dir=str(tmp_path),
                     on_trace_ready=lambda prof: None)
        p.start()
        for _ in range(3):
            with RecordEvent("matmul"):
                pass
            p.step()
        p.stop()
        out = p.summary()
        assert "matmul" in out and "ProfileStep" in out

    def test_step_info_ips(self, tmp_path):
        p = Profiler(targets=[ProfilerTarget.CPU], trace_dir=str(tmp_path),
                     on_trace_ready=lambda prof: None, timer_only=True)
        p.start()
        p.step(num_samples=32)
        p.step(num_samples=32)
        info = p.step_info()
        assert "ips" in info and "avg_cost" in info
        p.stop()

    def test_context_manager_with_repeat_windows(self, tmp_path):
        exports = []
        p = Profiler(targets=[ProfilerTarget.CPU],
                     scheduler=make_scheduler(closed=1, ready=0, record=1,
                                              repeat=2),
                     on_trace_ready=lambda prof: exports.append(
                         len(prof._events)),
                     trace_dir=str(tmp_path))
        with p:
            for _ in range(4):
                with RecordEvent("work"):
                    pass
                p.step()
        assert len(exports) == 2  # one flush per completed record window

    def test_windows_do_not_duplicate_events(self, tmp_path):
        """Each record window flushes only its own events (per-window
        reference semantics), and exports get unique filenames."""
        exports = []
        p = Profiler(targets=[ProfilerTarget.CPU],
                     scheduler=make_scheduler(closed=1, ready=0, record=1,
                                              repeat=2),
                     on_trace_ready=lambda prof: exports.append(
                         [n for n, _, _ in prof._events]),
                     trace_dir=str(tmp_path))
        with p:
            for i in range(4):
                if p.current_state.name.startswith("RECORD"):
                    with RecordEvent(f"work{i}"):
                        pass
                p.step()
        assert exports == [["work1"], ["work3"]]

    def test_engine_step_spans_and_counters_in_trace(self, tmp_path):
        """Serving steps appear in chrome traces: engine.step() pushes
        the engine gauges through record_counter (ph 'C' events + summary
        table) into the profiler's trace, and is a ``step`` span on the
        request-trace ring, which tools/trace_dump.py renders as ph 'X'
        slices (the span replaced RecordEvent('engine_step'), PR 25)."""
        import importlib.util

        from paddle_tpu.models import LlamaForCausalLM, llama_tiny
        from paddle_tpu.serving import ServingEngine, tracing

        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "trace_dump.py")
        spec = importlib.util.spec_from_file_location("td_prof", tools)
        trace_dump = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(trace_dump)

        paddle.seed(0)
        model = LlamaForCausalLM(llama_tiny(
            vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
            num_key_value_heads=2, max_position_embeddings=32))
        old = tracing.set_tracer(tracing.RequestTracer(capacity=1024))
        try:
            engine = ServingEngine(model, page_size=4, max_batch_slots=1)
            engine.add_request(np.arange(4), max_new_tokens=2)
            p = Profiler(targets=[ProfilerTarget.CPU],
                         on_trace_ready=export_chrome_tracing(str(tmp_path)),
                         trace_dir=str(tmp_path))
            p.start()
            n_steps = 0
            while engine.has_work:
                engine.step()
                p.step()
                n_steps += 1
            p.stop()
            ring = tracing.get_tracer().events()
        finally:
            tracing.set_tracer(old)
        files = [f for f in os.listdir(tmp_path)
                 if f.endswith(".paddle_trace.json")]
        assert files
        trace = load_profiler_result(os.path.join(tmp_path, files[0]))
        assert not [e for e in trace["traceEvents"]
                    if e["name"] == "engine_step"]
        counters = {e["name"] for e in trace["traceEvents"]
                    if e["ph"] == "C"}
        assert "serving.queue_depth" in counters
        assert "serving.tokens_per_sec" in counters
        assert "serving.queue_depth" in p.summary()
        doc, problems = trace_dump.chrome_trace(ring)
        assert problems == []
        spans = [e for e in doc["traceEvents"]
                 if e["name"] == "step" and e["ph"] == "X"]
        assert len(spans) == n_steps, "one step span per engine.step()"
        assert all(e["dur"] > 0 and e["args"]["key"] == engine.engine_id
                   for e in spans)
        assert sum(e["args"]["landed"] for e in spans) == 2

    def test_record_counter_noop_without_profiler(self):
        from paddle_tpu.profiler import record_counter

        record_counter("orphan.gauge", 1.0)  # must not raise

    def test_step_events_exported_with_timestamps(self, tmp_path):
        p = Profiler(targets=[ProfilerTarget.CPU],
                     on_trace_ready=export_chrome_tracing(str(tmp_path)),
                     trace_dir=str(tmp_path))
        p.start()
        for _ in range(3):
            with RecordEvent("op"):
                pass
            p.step()
        p.stop()
        files = [f for f in os.listdir(tmp_path)
                 if f.endswith(".paddle_trace.json")]
        trace = load_profiler_result(os.path.join(tmp_path, files[0]))
        steps = [e for e in trace["traceEvents"] if e["cat"] == "step"]
        assert len(steps) == 3
        assert all(e["ts"] > 0 and e["dur"] >= 0 for e in steps)
