"""Set-up: seconds of the run's ``step.trace`` spans on the program's trace ring."""
from benchmarks.layer_metrics.program_parts import setup_trace_s as read  # noqa: F401
