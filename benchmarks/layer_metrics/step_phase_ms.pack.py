"""Host loop: mean ``step.pack`` span over the window's steps."""
from benchmarks.harness.program_spans import phase_pack_ms as read  # noqa: F401
