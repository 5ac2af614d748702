"""Scheduler: mean ``step.plan`` span over the window's steps."""
from benchmarks.harness.program_spans import phase_plan_ms as read  # noqa: F401
