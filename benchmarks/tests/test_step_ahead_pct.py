"""``layer_metrics/step_ahead_pct.py``: the share of the window's steps that
launched while the previous step was still in flight, read from the ``ahead``
counter of each ``step`` span; ``None`` on a ring whose ``step`` spans carry
no such counter, as the program's before it existed."""
import pytest

from benchmarks.harness import spec
from benchmarks.tests.test_program_spans import (  # noqa: F401  (fixture)
    CHUNK, DECODE, _read, _routed_step, _run, ring)


def test_step_ahead_pct_reads_the_share_of_steps_launched_ahead(ring):
    """``ahead`` on the ``step`` span: 1 where the call launched its step
    with the previous one still in flight. A ring whose ``step`` spans
    carry no such counter (the program before it existed) reads None."""
    tr, clock = ring
    _routed_step(tr, clock, (2, 1, 6, 100, 3), DECODE + (0, 0))  # before
    _routed_step(tr, clock, (2, 1, 6, 100, 3), DECODE + (1, 0))
    _routed_step(tr, clock, (4, 3, 8, 400, 5), CHUNK + (0, 0))
    _routed_step(tr, clock, (2, 1, 6, 100, 3), DECODE + (1, 0))
    _routed_step(tr, clock, (2, 1, 6, 100, 3), DECODE + (1, 2))
    assert _read("step_ahead_pct", _run(4)) == pytest.approx(75.0)
    assert _read("step_ahead_pct", _run(2)) == pytest.approx(100.0)
    assert _read("step_ahead_pct", _run(5, kind="train")) is None
    _routed_step(tr, clock, (2, 1, 6, 100, 3), DECODE)   # no counter
    assert _read("step_ahead_pct", _run(2)) is None
    assert _read("step_ahead_pct", _run(1)) is None


def test_step_ahead_pct_is_listed_for_the_serve_cell_only():
    listed = {m["name"]: m for m in
              spec.load_cell("gpt3-1.3b.serve-decode").per_layer}
    assert listed["step_ahead_pct"]["layer"] == "host loop"
    assert listed["step_ahead_pct"]["moves"] == "serve_tokens_per_s"
    assert "step_ahead_pct" not in {
        m["name"] for m in spec.load_cell("gpt3-1.3b.train").per_layer}
