"""``layer_metrics/recurrent_block_rows_pct.py``: of the window's prompt-chunk
rows that advanced a per-slot state, the share the delta-rule kernel advanced
a block at a time, read from the ``state_block_rows`` and ``state_chunk_rows``
counters of each ``step`` span; ``None`` on a ring whose ``step`` spans carry
no such counter, as the program's before it existed."""
import pytest

from benchmarks.harness import spec
from benchmarks.tests.test_program_spans import (  # noqa: F401  (fixture)
    CHUNK, DECODE, _read, _routed_step, _run, ring)

#       ahead discarded (after the grid's counters, ``landed`` their last)
TAIL = (1, 0)


def test_recurrent_block_rows_pct_reads_the_share_of_chunk_rows(ring):
    """The state counters follow the grid's: rows, chunk rows, runs, and
    the rows that advanced in blocks (a 33-row chunk below a 64-row block
    takes none, a 200-row chunk all)."""
    tr, clock = ring
    ms = (2, 1, 6, 100, 3)
    _routed_step(tr, clock, ms, DECODE + TAIL + (8, 0, 8, 0))
    _routed_step(tr, clock, ms, CHUNK + TAIL + (40, 33, 8, 0))
    _routed_step(tr, clock, ms, CHUNK + TAIL + (207, 200, 8, 200))
    _routed_step(tr, clock, ms, DECODE + TAIL + (8, 0, 8, 0))
    assert _read("recurrent_block_rows_pct", _run(4)) == \
        pytest.approx(100.0 * 200 / 233)
    assert _read("recurrent_block_rows_pct", _run(2)) == \
        pytest.approx(100.0 * 200 / 200)
    # a window that advanced no chunk row, and a train run, read nothing
    assert _read("recurrent_block_rows_pct", _run(1)) is None
    assert _read("recurrent_block_rows_pct", _run(4, kind="train")) is None


def test_recurrent_block_rows_pct_is_none_without_the_counter(ring):
    """The parent program's ring: the state counters end at
    ``state_segments``, or there are none (a pool of pages alone)."""
    tr, clock = ring
    ms = (2, 1, 6, 100, 3)
    _routed_step(tr, clock, ms, CHUNK + TAIL + (40, 33, 8))
    _routed_step(tr, clock, ms, DECODE + TAIL + (8, 0, 8))
    assert _read("recurrent_block_rows_pct", _run(2)) is None
    _routed_step(tr, clock, ms, CHUNK + TAIL)
    assert _read("recurrent_block_rows_pct", _run(1)) is None


def test_recurrent_block_rows_pct_is_listed_for_the_olmo_cell_only():
    listed = {m["name"]: m for m in
              spec.load_cell("olmo-hybrid-7b.serve-chat32").per_layer}
    assert listed["recurrent_block_rows_pct"]["layer"] == "kernels"
    assert listed["recurrent_block_rows_pct"]["moves"] == "itl_p95_ms"
    assert "recurrent_block_rows_pct" not in {
        m["name"] for m in spec.load_cell("gpt3-1.3b.serve-decode").per_layer}
