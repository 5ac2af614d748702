"""Host loop: the share of the window's steps that launched their step while
the previous one was still on the device (the ``ahead`` counter of each
``step`` span), in percent. A program whose ``step`` span carries no such
counter reads nothing."""
from benchmarks.harness.program_spans import window_steps


def read(run):
    w = window_steps(run)
    if w is None or any("ahead" not in s["counts"] for s in w.steps):
        return None
    return 100.0 * w.counter("ahead") / len(w.steps)
