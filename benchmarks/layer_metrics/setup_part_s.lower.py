"""Set-up: seconds of the run's ``step.lower`` spans on the program's trace ring."""
from benchmarks.layer_metrics.program_parts import setup_lower_s as read  # noqa: F401
