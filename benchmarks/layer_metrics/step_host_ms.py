"""Host loop: mean over the window's steps of the router's ``sweep`` span less
``step.wait`` (the program's spans on its request-trace ring)."""
from benchmarks.harness.program_spans import step_host_ms as read  # noqa: F401
