"""Kernels: of the window's prompt-chunk rows that advanced a per-slot
recurrent state (the ``state_chunk_rows`` counter of each ``step`` span),
the share that the delta-rule kernel advanced a block of rows at a time in
the chunkwise form (``state_block_rows``), in percent: how much of the
chunk path runs on the MXU. A program whose ``step`` span carries no such
counter, or a window that advanced no chunk row, reads nothing."""
from benchmarks.harness.program_spans import window_steps


def read(run):
    w = window_steps(run)
    if w is None or any("state_block_rows" not in s["counts"]
                        for s in w.steps) \
            or not w.counter("state_chunk_rows"):
        return None
    return 100.0 * w.counter("state_block_rows") / w.counter(
        "state_chunk_rows")
