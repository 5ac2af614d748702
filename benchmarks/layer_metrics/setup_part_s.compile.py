"""Set-up: seconds of the run's ``step.compile`` and ``step.load`` spans on
the program's trace ring."""
from benchmarks.layer_metrics.program_parts import setup_compile_s as read  # noqa: F401
