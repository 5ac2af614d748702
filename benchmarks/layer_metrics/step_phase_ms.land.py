"""Host loop: mean ``step.land`` span over the window's steps."""
from benchmarks.harness.program_spans import phase_land_ms as read  # noqa: F401
