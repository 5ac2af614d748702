"""Host loop: mean ``step.dispatch`` span over the window's steps."""
from benchmarks.harness.program_spans import phase_dispatch_ms as read  # noqa: F401
