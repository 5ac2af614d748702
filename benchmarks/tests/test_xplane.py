"""The trace reduction: interval arithmetic on made-up events, and the whole
reduction on two small traces recorded on a v5e (data/tiny_v5e.xplane.pb: a
jitted matmul loop under ``bench.window`` and ``bench.step`` spans;
data/tiny_v5e_steps.xplane.pb: the same under the program's own step spans)."""
import os

import pytest

from benchmarks.harness import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "tiny_v5e.xplane.pb")
STEPS = os.path.join(HERE, "data", "tiny_v5e_steps.xplane.pb")


def test_union_merges_overlaps():
    assert xplane._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_self_time_takes_children_out_of_a_parent():
    # a while op [0, 100] encloses two body ops; self time is what is left
    events = [(0, 100, "while"), (10, 30, "fusion"), (40, 90, "custom-call")]
    out = xplane._self_times(events, 0, 1000)
    assert out == {"while": 30, "fusion": 20, "custom-call": 50}


def test_self_time_is_clipped_to_the_window():
    out = xplane._self_times([(0, 10, "a"), (20, 40, "b")], 5, 30)
    assert out == {"a": 5, "b": 10}


def test_idle_gaps_are_split_among_the_innermost_host_spans_by_overlap():
    busy = [(10, 20), (50, 60)]
    spans = [(0, 45, "bench.outer"), (22, 40, "bench.inner")]
    out = xplane._idle_by_span(busy, 0, 100, spans)
    # [0,10] outer; [20,50] = outer 2 + inner 18 + outer 5 + outside 5;
    # [60,100] outside
    assert out == {"bench.outer": 17, "bench.inner": 18, "outside": 45}


def test_a_gap_is_charged_to_the_programs_phases_under_the_harness_span():
    # one router step: the device runs [32, 70], inside step.wait
    spans = [(0, 100, "bench.router_step"), (2, 96, "sweep"), (4, 92, "step"),
             (4, 10, "step.plan"), (10, 30, "step.dispatch"),
             (30, 80, "step.wait"), (80, 92, "step.land")]
    out = xplane._idle_by_span([(32, 70)], 0, 100, spans)
    assert out == {"bench.router_step": 2 + 4, "sweep": 2 + 4,
                   "step.plan": 6, "step.dispatch": 20,
                   "step.wait": 2 + 10, "step.land": 12}
    assert sum(out.values()) == 100 - 38


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_v5e_trace_reduces():
    s = xplane.summarize(DATA, 1)
    assert s.n_chips == 1 and s.n_events > 0
    assert 0.0 < s.busy_s <= s.window_s
    assert abs(sum(s.op_seconds.values()) - s.busy_s) < 0.02 * s.busy_s
    assert s.seconds_of("fusion", "dot", "convolution") > 0.0
    b = s.breakdown()
    assert len(b["device_ops"]) <= 10 and b["idle_gaps"]


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_trace_idle_is_all_charged_and_sums_to_window_less_busy():
    s = xplane.summarize(DATA, 1)
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9)
    # eight bench.step spans, each ending after its own device work: the
    # idle inside them is theirs, the rest of the window is outside any span
    assert set(s.idle_by_span) == {"bench.step", "outside"}
    assert 0.0 < s.idle_by_span["bench.step"] < s.idle_by_span["outside"]


@pytest.mark.skipif(not os.path.exists(STEPS), reason="no recorded trace")
def test_recorded_trace_with_program_spans_names_the_programs_phases():
    """data/tiny_v5e_steps.xplane.pb: six router steps recorded on a v5e, each
    ``bench.router_step`` > ``sweep`` > ``step`` > ``step.plan`` ..
    ``step.land`` as ``RequestTracer`` writes them, a jitted matmul chain
    launched in ``step.dispatch`` and awaited in ``step.wait``."""
    s = xplane.summarize(STEPS, 1)
    idle = s.idle_by_span
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s,
                                               rel=1e-9)
    # the host's phases are named, not lumped under the harness's span
    assert {"step.plan", "step.pack", "step.dispatch", "step.land",
            "bench.submit"} <= set(idle)
    assert idle.get("bench.router_step", 0.0) < 0.5 * sum(idle.values())
    # the device works while the host waits: least idle of the phases there
    assert idle.get("step.wait", 0.0) < idle["step.plan"]


def test_op_family_drops_the_number_and_marks_mosaic_kernels():
    assert xplane.op_family(
        "%convolution_add_fusion.10 = bf16[4,1024]{1,0} fusion(...)") == \
        "convolution_add_fusion"
    assert xplane.op_family(
        '%paged_attention.24 = bf16[8,16,1,128] custom-call(s32[1024] %b), '
        'custom_call_target="tpu_custom_call"') == "mosaic:paged_attention"
    assert xplane.op_family("%while.9 = (u32[]) while(...)") == "while"
