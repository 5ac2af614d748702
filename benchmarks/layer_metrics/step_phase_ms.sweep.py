"""Host loop: mean of the router's ``sweep`` span less its ``step`` children."""
from benchmarks.harness.program_spans import phase_sweep_ms as read  # noqa: F401
