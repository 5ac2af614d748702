"""Scheduler: of the window's rows that advanced a per-slot recurrent state
(the ``state_rows`` counter of each ``step`` span), the share that were rows
of prompt chunks (``state_chunk_rows``), in percent: whether the delta-rule
kernel's time goes to the decode path or to the chunk path. A program whose
``step`` span carries no such counter, or a window that advanced no state,
reads nothing."""
from benchmarks.harness.program_spans import window_steps


def read(run):
    w = window_steps(run)
    if w is None or any("state_rows" not in s["counts"] for s in w.steps) \
            or not w.counter("state_rows"):
        return None
    return 100.0 * w.counter("state_chunk_rows") / w.counter("state_rows")
