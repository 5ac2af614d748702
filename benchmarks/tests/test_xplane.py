"""The trace reduction: interval arithmetic on made-up events, and the whole
reduction on a small trace recorded on a v5e (data/tiny_v5e.xplane.pb: a
jitted matmul loop under ``bench.window`` and ``bench.step`` spans)."""
import os

import pytest

from benchmarks.harness import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny_v5e.xplane.pb")


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


def test_idle_gaps_go_to_the_innermost_host_span():
    busy = [(10, 20), (50, 60)]
    spans = [(0, 45, "bench.outer"), (22, 40, "bench.inner")]
    out = xplane._idle_by_span(busy, 0, 100, spans)
    # [0,10] mid 5 -> outer; [20,50] mid 35 -> inner; [60,100] -> outside
    assert out == {"bench.outer": 10, "bench.inner": 30, "outside": 40}


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_v5e_trace_reduces():
    s = xplane.summarize(DATA, 1)
    assert s.n_chips == 1 and s.n_events > 0
    assert 0.0 < s.busy_s <= s.window_s
    assert abs(sum(s.op_seconds.values()) - s.busy_s) < 0.02 * s.busy_s
    assert s.seconds_of("fusion", "dot", "convolution") > 0.0
    b = s.breakdown()
    assert len(b["device_ops"]) <= 10 and b["idle_gaps"]


def test_op_family_drops_the_number_and_marks_mosaic_kernels():
    assert xplane.op_family(
        "%convolution_add_fusion.10 = bf16[4,1024]{1,0} fusion(...)") == \
        "convolution_add_fusion"
    assert xplane.op_family(
        '%paged_attention.24 = bf16[8,16,1,128] custom-call(s32[1024] %b), '
        'custom_call_target="tpu_custom_call"') == "mosaic:paged_attention"
    assert xplane.op_family("%while.9 = (u32[]) while(...)") == "while"
