"""Step program: mean ``step.wait`` span (blocked on the device) over the
window's steps."""
from benchmarks.harness.program_spans import phase_wait_ms as read  # noqa: F401
